"""Quickstart: the SU3 engine (the paper's workload) through the public API.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs.su3_bench import SMOKE_L8
from repro.core import roofline
from repro.core.su3.engine import SU3Engine
from repro.kernels import ops, ref


def main() -> None:
    print(f"devices: {jax.devices()}")

    # 1. the kernel, canonical complex form, vs the oracle
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (1024, 4, 3, 3, 2))
    a = jax.lax.complex(a[..., 0], a[..., 1])
    b = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 3, 2))
    b = jax.lax.complex(b[..., 0], b[..., 1])
    c = ops.su3_mult(a, b)  # Pallas kernel (interpret mode on CPU)
    err = float(abs(c - ref.su3_mult_ref(a, b)).max())
    print(f"pallas vs oracle max err: {err:.2e}")

    # 2. the paper's benchmark loop (L=8 smoke config)
    result = SU3Engine(SMOKE_L8).run()
    print(f"engine: {result.row()}")

    # 3. the three-term roofline for the paper's L=32 on TPU v5e
    rep = roofline.analytic_su3_report(
        n_sites=32**4, word_bytes=4, bytes_per_site_rw=576, n_chips=1
    )
    print(rep.summary())
    print(f"v5e bandwidth-bound GF/s (SoA): "
          f"{roofline.TPU_V5E.hbm_bw * (864 / 576) / 1e9:.0f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
