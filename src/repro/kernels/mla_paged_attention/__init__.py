from repro.kernels.mla_paged_attention.ops import (  # noqa: F401
    mla_paged_attention,
    mla_paged_decode_step,
)
