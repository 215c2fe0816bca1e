"""L5 tile kernels — tpu_blas / tpu_lapack (reference ``blas/tile.h``,
``lapack/tile.h`` and the custom-kernel layer):

* :mod:`.blas` — level-3 ops (gemm/hemm/her2k/herk/trmm/trsm), the
  mxu-routable ``mm``/``contract``/``trsm_panel`` entry points.
* :mod:`.lapack` — potrf(+info), hegst, laset/lacpy, lange/lantr, larft,
  laed4, stedc (host), and friends.
* :mod:`.ozaki` — emulated-f64/c128 gemm on the int8 MXU (error-free
  slicing).
* :mod:`.mixed` — mixed-precision panel potrf / triangular inverse
  (half-precision seed + Newton).
* :mod:`.pallas_kernels` — predicated trailing-update Pallas kernel.
"""

from . import blas, lapack, mixed, ozaki  # noqa: F401

__all__ = ["blas", "lapack", "mixed", "ozaki"]
