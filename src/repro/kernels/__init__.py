# The one custom kernel: channel_ring/ (kernel.py + ops.py + ref.py), the
# fused ring commit every protocol's tick runs (Pallas on the TPU, the
# pure-jnp scatter oracle elsewhere).
