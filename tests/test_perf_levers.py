"""§Perf levers (seq-sharded attention, flash-decoding cache layout) must be
numerically identical to the baseline paths.  Runs in a subprocess with 8
forced host devices so the main test process keeps seeing 1 device.

Also home to host-side perf-lever regressions that need no devices at all:
the scheduler's select_window must stay one rebuild pass over the queue
(O(queue) per boundary), not the per-pick ``list.remove`` scan it shipped
with (O(picked x queue))."""
import os
import subprocess
import sys
import textwrap
import time

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as tf, attention as attn
    from repro.models.layers import ShardCtx
    from repro.launch.mesh import make_demo_mesh
    from repro.parallel import sharding as shd

    mesh = make_demo_mesh(2, 4)
    ctx_qs = ShardCtx(mesh=mesh, batch_axes=("data",), seq_shard_attn=True)
    key = jax.random.PRNGKey(0)
    b, s, h, kv, hd = 2, 64, 6, 6, 16      # 6 heads % 4 != 0 -> qshard path
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    with jax.set_mesh(mesh):
        for w in (0, 24):
            o_ref = attn.blockwise_attention(q, k, v, causal=True, window=w)
            o_qs = attn.qshard_attention(q, k, v, ctx_qs, causal=True,
                                         window=w)
            err = float(jnp.abs(o_qs - o_ref).max())
            assert err < 2e-5, ("qshard", w, err)

    cfg = get_config("granite-3-8b").reduced()
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                              cfg.vocab_size)
    ref, _ = tf.forward(params, {"tokens": toks}, cfg)
    ctx_cs = ShardCtx(mesh=mesh, batch_axes=("data",), cache_seq_shard=True)
    with jax.set_mesh(mesh):
        cache = tf.init_cache(cfg, 4, 16)
        cache = jax.device_put(
            cache, shd.to_shardings(shd.cache_specs(cache, ctx_cs), mesh))
        dec = jax.jit(lambda p, c, t, i: tf.decode_step(
            p, c, {"tokens": t}, i, cfg, ctx_cs))
        outs = []
        for i in range(16):
            lg, cache = dec(params, cache, toks[:, i:i + 1], jnp.int32(i))
            outs.append(lg[:, 0])
        d = jnp.stack(outs, axis=1)
        err = float(jnp.abs(d - ref).max())
        assert err < 2e-3, ("cache_seq_shard", err)
    print("LEVERS-OK")
""")


def _loaded_fifo(n):
    """A depth-n FIFO queue built directly (bypassing add()'s per-insert
    sort, which would dominate the timing and is not what this test
    regresses)."""
    from repro.serve import FIFOScheduler, Request
    sch = FIFOScheduler()
    sch._queue = [Request(req_id=i, key=None, arrival_tick=0)
                  for i in range(n)]
    sch._order = {i: i for i in range(n)}
    return sch


def test_select_window_scales_linearly_in_queue_depth():
    """One select_window over a depth-n queue is O(n): a 4x deeper queue
    must not cost anywhere near the 16x of the old per-pick
    ``list.remove`` scan.  Wall-clock bounds are generous (CI noise) but
    far below the quadratic path's cost at this depth."""
    def one_call(n):
        sch = _loaded_fifo(n)
        t0 = time.perf_counter()
        picked = sch.select_window(n, now=0, window=1)
        dt = time.perf_counter() - t0
        assert len(picked) == n and len(sch) == 0
        return dt
    one_call(1000)                                    # warmup
    t_small = min(one_call(4000) for _ in range(3))
    t_big = min(one_call(16000) for _ in range(3))
    assert t_big < 0.5, f"select_window(16k queue) took {t_big:.3f}s"
    assert t_big / max(t_small, 1e-6) < 10.0, \
        f"super-linear queue scaling: {t_small:.4f}s -> {t_big:.4f}s"


@pytest.mark.slow
def test_perf_levers_match_baseline():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "LEVERS-OK" in out.stdout
