"""Public jit'd wrappers for the Pallas kernels.

The kernels compile to Mosaic for a TPU.  ``interpret=True`` runs the
kernel body in Python instead (correctness checks on a CPU backend); it is
never chosen for the caller, so a kernel that cannot compile fails loudly
instead of silently running interpreted.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.chunked_matmul import chunked_matmul as _cm_kernel
from repro.kernels.flash_attention import flash_attention as _fa_kernel
from repro.kernels.paged_attention import paged_attention as _pa_kernel


def chunked_matmul(x, w, *, bm=128, bn=128, bk=128, interpret=False):
    """C = X Wᵀ via the chunked relational GEMM kernel (pads to tiles)."""
    M, K = x.shape
    N = w.shape[0]
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    pm, pn, pk = (-M) % bm_, (-N) % bn_, (-K) % bk_
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    wp = jnp.pad(w, ((0, pn), (0, pk))) if (pn or pk) else w
    out = _cm_kernel(xp, wp, bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return out[:M, :N]


def flash_attention(q, k, v, *, causal=True, bq=128, bk=128,
                    interpret=False):
    return _fa_kernel(q, k, v, causal=causal, bq=bq, bk=bk,
                      interpret=interpret)


def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    interpret=False):
    return _pa_kernel(q, k_pool, v_pool, page_table, lengths,
                      interpret=interpret)

