from repro.kernels.paged_attention.ops import (KERNEL_KINDS,
                                               latent_kernel_counts,
                                               make_sharded_paged_attention,
                                               modeled_hbm_bytes,
                                               paged_attention,
                                               paged_latent_attention,
                                               resolve_kernel,
                                               sharded_paged_specs)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_latent_attention_ref)

__all__ = ["KERNEL_KINDS", "latent_kernel_counts",
           "make_sharded_paged_attention",
           "modeled_hbm_bytes", "paged_attention", "paged_attention_ref",
           "paged_latent_attention", "paged_latent_attention_ref",
           "resolve_kernel", "sharded_paged_specs"]
