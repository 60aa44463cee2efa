"""Multi-device execution tests for the distributed substrate.

These run in a subprocess with 8 forced host devices (the main test
process must keep the default single device — see conftest.py).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_pipeline_parallel_matches_sequential():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("pod", "model"))
        L, B, D = 8, 8, 16
        w = jax.random.normal(jax.random.key(0), (L, D, D)) * 0.3
        x = jax.random.normal(jax.random.key(1), (B, D))

        def layer(lw, h):
            return jnp.tanh(h @ lw)

        ref = x
        for i in range(L):
            ref = layer(w[i], ref)
        got = pipeline_apply(layer, w, x, mesh, axis="pod", microbatches=4)
        np.testing.assert_allclose(np.array(got), np.array(ref),
                                   rtol=1e-4, atol=1e-5)
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_sharded_flash_decode_matches_oracle():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.collectives import sharded_flash_decode
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        B, H, S, D = 2, 4, 64, 16
        q = jax.random.normal(jax.random.key(0), (B, H, D))
        k = jax.random.normal(jax.random.key(1), (B, S, D))
        v = jax.random.normal(jax.random.key(2), (B, S, D))
        valid = jnp.arange(S)[None] < jnp.array([64, 40])[:, None]
        got = sharded_flash_decode(mesh, "data", q, k, v, valid, 0.25)
        s = jnp.einsum("bhd,bsd->bhs", q, k) * 0.25
        s = jnp.where(valid[:, None], s, -2e38)
        w = jax.nn.softmax(s, -1)
        ref = jnp.einsum("bhs,bsd->bhd", w, v)
        np.testing.assert_allclose(np.array(got), np.array(ref),
                                   rtol=1e-5, atol=1e-5)
        print("FLASH_OK")
    """)
    assert "FLASH_OK" in out


def test_dryrun_entrypoint_small_cell():
    """The dry-run CLI itself (with its own 512-device env) stays green."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["REPRO_XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3-0.6b",
         "--shape", "decode_32k"],
        capture_output=True, text=True, env=env, timeout=580)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "1 ok, 0 skipped, 0 errors" in out.stdout


def test_compression_under_psum():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import (compress_grads,
                                                   decompress_grads, init_ef)
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        g = {"w": jax.random.normal(jax.random.key(0), (8, 64))}

        def allreduce_compressed(gs):
            # per-shard quantize -> dequantized mean across shards
            q, s, _ = compress_grads(gs, init_ef(gs))
            deq = decompress_grads(q, s)
            return jax.tree.map(lambda x: jax.lax.pmean(x, "data"), deq)

        fn = jax.shard_map(allreduce_compressed, mesh=mesh,
                           in_specs=({"w": P("data")},),
                           out_specs={"w": P("data")}, check_vma=False)
        got = fn(g)
        # reference: the true mean across shards (rows), tiled back
        ref = jnp.broadcast_to(jnp.mean(g["w"], axis=0, keepdims=True),
                               g["w"].shape)
        np.testing.assert_allclose(np.array(got["w"]), np.array(ref),
                                   atol=0.02)
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out


# ---------------------------------------------------------------------------
# Reference-quantizer edge cases (single process — quantize_rows is the
# cache tier's reference quantizer, so its corners are contract surface)
# ---------------------------------------------------------------------------

def _cmp():
    import jax  # noqa: F401  (keeps the lazy import pattern of this file)
    from repro.distributed import compression as cmp
    return cmp


def test_quantize_rows_all_zero_page_roundtrips_exactly():
    import jax.numpy as jnp
    import numpy as np
    cmp = _cmp()
    x = jnp.zeros((2, 4, 8), jnp.bfloat16)       # an all-zero host page
    for dt in cmp.CACHE_QUANT_DTYPES.values():
        q, s = cmp.quantize_rows(x, dt)
        assert s.shape == (2, 4, 1) and s.dtype == cmp.SCALE_DTYPE
        np.testing.assert_array_equal(np.array(q, np.int32), 0)
        np.testing.assert_array_equal(np.array(s, np.float32), 0.0)
        deq = cmp.dequantize_rows(q, s, jnp.bfloat16)
        np.testing.assert_array_equal(np.array(deq, np.float32), 0.0)


def test_quantize_rows_sentinel_rows_keep_zero_scale():
    # zero rows *inside* a page of live rows stay exactly zero — the
    # paged tier's unwritten/sentinel rows must survive the round trip
    import jax.numpy as jnp
    import numpy as np
    cmp = _cmp()
    x = jnp.stack([jnp.zeros((8,)), jnp.full((8,), 3.0),
                   jnp.zeros((8,))]).astype(jnp.bfloat16)
    q, s = cmp.quantize_rows(x, jnp.int8)
    sf = np.array(s, np.float32).ravel()
    assert sf[0] == 0.0 and sf[2] == 0.0 and sf[1] > 0.0
    deq = np.array(cmp.dequantize_rows(q, s, jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(deq[0], 0.0)
    np.testing.assert_array_equal(deq[2], 0.0)
    np.testing.assert_allclose(deq[1], 3.0, rtol=2e-2)


def test_quantize_rows_max_magnitude_clips_not_wraps():
    # the f16-rounded stored scale can land *below* amax/qmax; the
    # payload must clip to the dtype's max magnitude, never overflow
    import jax.numpy as jnp
    import numpy as np
    cmp = _cmp()
    x = jnp.array([[1000.0, -1000.0, 999.9, 0.25]], jnp.float32)
    for name, dt in cmp.CACHE_QUANT_DTYPES.items():
        q, s = cmp.quantize_rows(x, dt)
        qf = np.array(q, np.float32)
        m = cmp.quant_max(dt)
        assert np.abs(qf).max() <= m
        assert qf[0, 0] == m and qf[0, 1] == -m          # amax hits the rail
        deq = np.array(cmp.dequantize_rows(q, s, jnp.float32))
        np.testing.assert_allclose(deq[0, :2], [1000.0, -1000.0],
                                   rtol=1e-2)
        # small elements keep their sign and scale-bounded error
        assert abs(deq[0, 3] - 0.25) <= np.array(s, np.float32)[0, 0]


def test_quantize_rows_negative_only_rows():
    # amax from a negative extremum: symmetric quantization must not
    # bias the sign or saturate one-sided
    import jax
    import jax.numpy as jnp
    import numpy as np
    cmp = _cmp()
    x = -jnp.abs(jax.random.normal(jax.random.key(3), (5, 16),
                                   jnp.float32)) - 0.1
    q, s = cmp.quantize_rows(x.astype(jnp.bfloat16), jnp.int8)
    deq = np.array(cmp.dequantize_rows(q, s, jnp.float32))
    assert (deq <= 0).all()
    err = np.abs(deq - np.array(x, np.float32))
    bound = np.array(s, np.float32) * 0.5 + np.abs(np.array(x)) * 0.01
    assert (err <= bound).all()
