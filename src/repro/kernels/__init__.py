# Pallas TPU kernels for the framework's compute hot-spots:
#   flash_attention — causal/windowed attention, forward and backward (splash)
#   ssd_scan        — Mamba2 SSD chunked scan (grid-carried state scratch)
#   vrl_update      — fused VRL-SGD local/sync updates (HBM-bound elementwise)
# ops.py = jit'd wrappers; ref.py = pure-jnp oracles; validated interpret=True.
from repro.kernels.ops import (  # noqa: F401
    mha_flash,
    ssd_chunk_scan,
    vrl_local_update_tree,
    vrl_sync_update_tree,
)
