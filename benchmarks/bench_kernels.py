"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels run in interpret mode (correctness
only — wall time is meaningless), so the timed numbers are the jitted
pure-JAX twin implementations; each row also re-asserts allclose between
kernel and oracle so the benchmark doubles as a health check.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.kernels import ref
from repro.kernels.anytime_svm import anytime_svm_scores
from repro.kernels.perforated_attention import perforated_attention
from repro.models.attention import flash_attention
from repro.models.rwkv import wkv_scan
from repro.models.ssm import ssd_scan


def main() -> dict:
    out = {}
    ks = jax.random.split(jax.random.key(0), 8)

    # attention: pure-JAX flash path (the dry-run path), 1k seq
    B, S, H, Dh = 1, 1024, 8, 64
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, Dh), jnp.float32)
    fa = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                                 chunk=256))
    us = timeit(fa, q, k, v)
    emit("kernels.flash_attention_jax_1k", us,
         f"{2 * 2 * B * H * S * S * Dh / 2 / (us / 1e6) / 1e9:.1f}GFLOP/s")

    # perforated attention kernel (interpret): correctness + skip accounting
    qs = q.transpose(0, 2, 1, 3)[:, :2, :256]
    keep = jnp.array([1, 0], jnp.int32)
    got = perforated_attention(qs, qs, qs, keep, causal=True,
                               interpret=True)
    want = ref.perforated_attention_ref(qs, qs, qs, keep.astype(bool),
                                        causal=True, block=128)
    ok = bool(np.allclose(got, want, atol=2e-5))
    emit("kernels.perforated_attention_allclose", 0.0, str(ok))

    # anytime svm kernel vs ref
    x = jax.random.normal(ks[3], (64, 256))
    w = jax.random.normal(ks[4], (6, 256))
    b = jnp.zeros((6,))
    got = anytime_svm_scores(x, w, b, 100, interpret=True)
    want = ref.anytime_svm_ref(x, w, b, 100)
    emit("kernels.anytime_svm_allclose", 0.0,
         str(bool(np.allclose(got, want, atol=1e-4))))
    svm = jax.jit(lambda xx: xx @ w.T)
    emit("kernels.svm_scores_jax_64x256", timeit(svm, x), "dense")

    # wkv chunked scan (pure-JAX twin)
    B2, L2, H2, N2 = 1, 512, 8, 64
    r = jax.random.normal(ks[5], (B2, L2, H2, N2))
    logw = -jnp.exp(jax.random.normal(ks[6], (B2, L2, H2, N2)))
    u = jax.random.normal(ks[7], (H2, N2))
    wkv = jax.jit(lambda a, b, c, d: wkv_scan(a, a, a, b, c, chunk=d)[0],
                  static_argnums=3)
    us = timeit(wkv, r, logw, u, 32)
    emit("kernels.wkv_scan_jax_512", us, f"chunk=32")

    # ssd chunked scan
    x3 = jax.random.normal(ks[0], (1, 512, 8, 64))
    dt3 = jax.nn.softplus(jax.random.normal(ks[1], (1, 512, 8)))
    A3 = jnp.exp(jax.random.normal(ks[2], (8,)) * 0.3)
    Bm = jax.random.normal(ks[3], (1, 512, 64))
    ssd = jax.jit(lambda a, b, c, d: ssd_scan(a, b, c, d, d, chunk=64)[0])
    us = timeit(ssd, x3, dt3, A3, Bm)
    emit("kernels.ssd_scan_jax_512", us, "chunk=64")

    # serve-tick megakernel (interpret) vs the quantized reference tick,
    # timed as the jitted q32 twin (the same integer numerics as XLA)
    from benchmarks.fleet_megakernel import _serve_tick_fixture
    tick_pallas, tick_q32, agree = _serve_tick_fixture(8192, interpret=True)
    emit("kernels.serve_tick_agrees_q32", 0.0, str(agree))
    emit("kernels.serve_tick_q32_twin_8k", timeit(tick_q32), "one tick")
    emit("kernels.serve_tick_interpret_8k", timeit(tick_pallas),
         "interpret: correctness only")
    return out


if __name__ == "__main__":
    main()
