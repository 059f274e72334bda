from repro.sharding.specs import (  # noqa: F401
    axis_rules,
    batch_spec,
    partition_specs,
    shardings,
    spec_for,
    worker_stacked_spec,
)
