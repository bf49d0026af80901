"""The replay's per-seed statistics, computed where the series lie
(``experiment._replay_stats``), against the numpy pooling of the pulled
series that they replace: exact order statistics, ``np.percentile``'s
interpolation, the masks, and the same numbers on a seed-sharded series."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.experiment import _order_stats, _replay_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INT32_MAX = np.iinfo(np.int32).max


def _numpy_pooling(rt, cpu, mem, hot, t_end, settle_ticks, num_windows):
    """The host reduction of the pulled series, seed by seed: RT samples
    above 0 on ticks in [30, t_end), np.percentile, window-level
    utilization spread over the arrival phase (over the real prefix for
    a trace too short to have one), hot windows of the real prefix.
    ``n`` is the seed's sample count."""
    padded_windows, span = rt.shape[1], rt.shape[2]
    tick = (np.arange(padded_windows)[:, None] * span
            + np.arange(span)[None, :])
    valid = (tick >= 30) & (tick < t_end)
    w_start = np.arange(padded_windows) * span
    wins = (w_start >= 30) & (w_start + span <= t_end - settle_ticks)
    if not wins.any():
        wins = np.arange(padded_windows) < num_windows
    out = []
    for i in range(rt.shape[0]):
        r = rt[i][valid]
        samples = r[r > 0]
        n = samples.size
        if n == 0:
            samples = np.full(1, np.nan)
        out.append({
            "n": n,
            "avg_rt": samples.mean(),
            "p90_rt": np.percentile(samples, 90),
            "p99_rt": np.percentile(samples, 99),
            "cpu_util_std": (100 * cpu[i][wins]).std(axis=1).mean(),
            "mem_util_std": (100 * mem[i][wins]).std(axis=1).mean(),
            "hot_windows": int(hot[i][:num_windows].any(-1).sum()),
        })
    return out


def _series(seed, batch=3, windows=6, span=40, nodes=3, slots=4):
    """A replay's outputs: log-normal RT with 30% zeros and rounded (tied)
    values in one slot, utilization in [0, 1), a few hot flags."""
    rng = np.random.default_rng(seed)
    rt = rng.lognormal(3.0, 1.0, (batch, windows, span, nodes, slots))
    rt = rt.astype(np.float32)
    rt[rng.random(rt.shape) < 0.3] = 0
    rt[..., 0] = np.round(rt[..., 0])
    cpu, mem = rng.random((2, batch, windows, nodes), dtype=np.float32)
    hot = rng.random((batch, windows, nodes)) < 0.05
    return rt, cpu, mem, hot


def _random(rt):
    return 187            # the last window padded past t_end


def _one_sample(rt):
    # seed 1 keeps one sample in [30, t_end); the warm-up and the padding
    # keep theirs, which must not count
    t_end = 187
    flat = rt[1].reshape(-1, *rt.shape[3:])
    flat[30:t_end] = 0
    flat[100, 1, 2] = 7.25
    return t_end


def _no_sample(rt):
    t_end = 150
    flat = rt[1].reshape(-1, *rt.shape[3:])
    flat[30:t_end] = 0
    return t_end


def _short_trace(rt):
    return 90             # no window lies wholly in the arrival phase


@pytest.mark.parametrize("case", [_random, _one_sample, _no_sample,
                                  _short_trace])
@pytest.mark.parametrize("seed", [0, 3_000_000_017])
def test_replay_stats_match_numpy_pooling(case, seed):
    rt, cpu, mem, hot = _series(seed % 2**32)
    t_end = case(rt)
    settle, num_windows = 40, -(-t_end // rt.shape[2])
    want = _numpy_pooling(rt, cpu, mem, hot, t_end, settle, num_windows)
    got = _replay_stats(jnp.asarray(rt), jnp.asarray(cpu), jnp.asarray(mem),
                        jnp.asarray(hot), np.int32(t_end), np.int32(settle),
                        np.int32(num_windows))
    got = {k: np.asarray(v) for k, v in got.items()}
    for i, w in enumerate(want):
        assert got["count"][i] == w["n"]
        assert got["hot_windows"][i] == w["hot_windows"]
        for k in ("cpu_util_std", "mem_util_std"):
            np.testing.assert_allclose(got[k][i], w[k], rtol=1e-6)
        if w["n"] == 0:
            for k in ("avg_rt", "p90_rt", "p99_rt"):
                assert np.isnan(got[k][i]) and np.isnan(w[k]), k
            continue
        np.testing.assert_allclose(got["avg_rt"][i], w["avg_rt"], rtol=1e-6)
        for k in ("p90_rt", "p99_rt"):
            assert got[k].dtype == np.float32
            np.testing.assert_array_max_ulp(got[k][i], w[k], maxulp=2)
    if case is _one_sample:
        assert got["p90_rt"][1] == got["p99_rt"][1] == np.float32(7.25)


def _np_rank(n, p):
    """np.percentile's lower index for ``p`` on ``n`` float32 samples."""
    h = np.float32(n - 1) * (np.float32(p) / np.float32(100))
    return int(np.floor(h))


@pytest.mark.parametrize("case", ["distinct", "ties", "extremes", "one"])
def test_order_stats_are_the_sorted_samples(case):
    """Every rank of every row is bitwise the sorted samples' entry, with
    excluded entries anywhere, ties, subnormals, +inf and single samples."""
    rng = np.random.default_rng(11)
    x = rng.lognormal(0.0, 3.0, (4, 5, 97)).astype(np.float32)
    if case == "ties":
        x = np.round(x)
    elif case == "extremes":
        x.reshape(4, -1)[:, :6] = [1e-45, 1e-40, 1.2e-38, 3.4e38, np.inf,
                                    1e-45]
    keep = (rng.random(x.shape) < 0.6) & (x > 0)
    if case == "one":
        keep[:] = False
        keep[np.arange(4), rng.integers(5, size=4), rng.integers(97, size=4)] \
            = True
    bits = np.where(keep, x.view(np.int32), INT32_MAX)
    ranks, want = [], []
    for row, k in zip(x, keep):
        s = np.sort(row[k])
        n = s.size
        lo90, lo99 = _np_rank(n, 90), _np_rank(n, 99)
        r = [0, n // 2, n - 1, lo90, min(lo90 + 1, n - 1), lo99,
             min(lo99 + 1, n - 1)]
        ranks.append(r)
        want.append(s[r])
    got = np.asarray(_order_stats(jnp.asarray(bits),
                                  jnp.asarray(ranks, jnp.int32)))
    np.testing.assert_array_equal(got.view(np.int32),
                                  np.asarray(want).view(np.int32))


def test_sharded_replay_stats_match_one_device_subprocess():
    """With 2 forced host devices, ``replay_plan_batched(devices=2)`` gives
    every seed the numbers ``devices=1`` gives, the pad-to-device-multiple
    path included (3 seeds), and the statistics program keeps a
    seed-sharded series where it lies (no collective gathers it).
    Subprocess because XLA_FLAGS must be set before jax loads."""
    code = textwrap.dedent("""\
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.cluster.experiment import _replay_stats, replay_plan_batched
        from repro.launch.mesh import make_seed_mesh

        assert jax.device_count() == 2, jax.device_count()
        plan = {"log": [("place_on", 0.0, 0, 0, 0, 300.0, 0.4),
                        ("place_on", 0.0, 1, 0, 1, 250.0, 0.1),
                        ("migrate_on", 200.0, 0, 0, 2, 0)],
                "t_end": 400.0, "num_nodes": 3}
        for seeds in ((1, 2, 3), (1, 2, 3, 4)):
            one = replay_plan_batched(plan, sim_seeds=seeds)
            two = replay_plan_batched(plan, sim_seeds=seeds, devices=2)
            assert two["seeds"] == one["seeds"], (one, two)

        mesh = make_seed_mesh(2)
        seeds, rep = NamedSharding(mesh, P("seeds")), NamedSharding(mesh, P())
        f32, i32 = jnp.float32, jnp.int32
        args = [jax.ShapeDtypeStruct((4, 16, 40, 3, 8), f32, sharding=seeds),
                jax.ShapeDtypeStruct((4, 16, 3), f32, sharding=seeds),
                jax.ShapeDtypeStruct((4, 16, 3), f32, sharding=seeds),
                jax.ShapeDtypeStruct((4, 16, 3), jnp.bool_, sharding=seeds)]
        args += [jax.ShapeDtypeStruct((), i32, sharding=rep)] * 3
        hlo = _replay_stats.lower(*args).compile().as_text()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert op not in hlo, op
        print("OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
