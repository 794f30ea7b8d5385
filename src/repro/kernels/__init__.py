# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
# Shipped triads (see README.md): flash_attention (prefill/train),
# paged_attention (serve decode through the paged KV pool), moe_gmm
# (grouped expert FFN).
import jax


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: everywhere but a TPU
    backend.  The model decides this once, at build time, from here; the
    serve engine reports it (``report()["engine"]["pallas_interpret"]``)
    so a fused run that lost its chip cannot pass for a chip run."""
    return jax.default_backend() != "tpu"
