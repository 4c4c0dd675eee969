"""Quickstart: hybrid SpMM/SDDMM on one matrix in four lines each.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import LibraSDDMM, LibraSpMM, nnz1_fraction
from repro.kernels import ref
from repro.sparse.generate import mixed_csr


def main() -> None:
    rng = np.random.default_rng(0)
    a = mixed_csr(256, 256, seed=1)  # hybrid-regime matrix (paper Fig. 1)
    print(f"matrix: {a.shape}, nnz={a.nnz}, "
          f"NNZ-1 fraction={nnz1_fraction(a):.2f}")

    # --- SpMM: C = A @ B ------------------------------------------------
    b = jnp.asarray(rng.standard_normal((a.k, 128)).astype(np.float32))
    spmm = LibraSpMM(a)                       # preprocess + autotune once
    cfg = spmm.tune_config                    # the model-tuned plan choice
    print(f"tuned: threshold={cfg.threshold} lane-tile cap={cfg.nt} "
          f"(source={cfg.source})")
    c = spmm(b)                               # fast XLA path
    c_pallas = spmm(b, backend="pallas")      # Pallas kernels (interpreted on CPU)
    oracle = ref.spmm_dense_oracle(a.to_dense(), np.asarray(b))
    print(f"SpMM: tc_ratio={spmm.tc_ratio:.2f} "
          f"max_err_xla={np.abs(np.asarray(c) - oracle).max():.2e} "
          f"max_err_pallas={np.abs(np.asarray(c_pallas) - oracle).max():.2e}")

    # --- SDDMM: vals = sample(X @ Yᵀ, A) --------------------------------
    x = jnp.asarray(rng.standard_normal((a.m, 64)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((a.k, 64)).astype(np.float32))
    sddmm = LibraSDDMM(a)
    vals = sddmm(x, y)
    so = ref.sddmm_dense_oracle(a.to_dense(), np.asarray(x), np.asarray(y))
    print(f"SDDMM: tc_ratio={sddmm.tc_ratio:.2f} "
          f"max_err={np.abs(np.asarray(vals) - so).max():.2e}")
    print("quickstart OK")


if __name__ == "__main__":
    main()
