"""End-to-end system tests: training converges on the synthetic corpus,
resumes exactly after a simulated failure, and serving with continuous
batching produces tokens; the dry-run path compiles on a small mesh."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_train_loss_decreases(tmp_path):
    from repro.launch.train import main
    out = main(["--arch", "qwen2-0.5b", "--reduced", "--steps", "25",
                "--batch", "4", "--seq", "64",
                "--ckpt-dir", str(tmp_path / "ck")])
    losses = out["losses"]
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


def test_train_restart_resumes(tmp_path):
    """Simulated failure: run 10 steps, 'crash', restart to 16 — the
    resumed run continues from the checkpoint, not from scratch."""
    from repro.launch.train import main
    ck = str(tmp_path / "ck")
    first = main(["--arch", "qwen2-0.5b", "--reduced", "--steps", "10",
                  "--batch", "4", "--seq", "64", "--ckpt-dir", ck,
                  "--ckpt-every", "5"])
    second = main(["--arch", "qwen2-0.5b", "--reduced", "--steps", "16",
                   "--batch", "4", "--seq", "64", "--ckpt-dir", ck,
                   "--ckpt-every", "5"])
    # resumed run executed only steps 10..16
    assert len(second["losses"]) == 6
    # and continued improving from where the first left off
    assert second["losses"][-1] < first["losses"][0]


def test_serve_continuous_batching():
    from repro.launch.serve import main
    out = main(["--arch", "qwen2-0.5b", "--reduced", "--requests", "5",
                "--batch", "2", "--prompt-len", "8", "--gen", "6"])
    lens = [len(v) for v in out["outputs"].values()]
    assert sorted(lens, reverse=True)[:4] == [6, 6, 6, 6]
    assert sum(lens) >= 5 * 6 - 6  # last slot may hit the cache limit
    # flight-recorder metrics: per-request latency summary is populated
    lat = out["latency_s"]
    assert lat["count"] >= 4
    assert 0 < lat["mean_s"] <= lat["max_s"] <= lat["p99_s"] * 2 + 1e-9
    assert lat["p50_s"] > 0


def test_dryrun_cell_compiles_small_mesh():
    """Run the dry-run code path in a subprocess with 8 fake devices and a
    reduced config: proves lower+compile+analysis works end-to-end without
    the 512-device production mesh (which the full sweep covers)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_config
from repro.launch.steps import ShapeSpec, input_specs, make_train_step
from repro.launch.hloanalysis import analyze
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((4, 2), ("data", "model"))
cfg = get_config("qwen3-4b", reduced=True)
shape = ShapeSpec("tiny_train", "train", 64, 8)
with mesh:
    sp = input_specs(cfg, shape, mesh)
    fn = make_train_step(cfg)
    compiled = jax.jit(fn).lower(sp["params"], sp["opt_state"],
                                 sp["batch"]).compile()
costs = analyze(compiled.as_text())
assert costs.dot_flops > 0
assert compiled.memory_analysis() is not None
print("OK", costs.dot_flops)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=480)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


@pytest.mark.parametrize("explicit", [False, True])
def test_embed_gathers_a_row_sharded_table(explicit):
    """The embedding table is sharded on its rows.  Under Explicit mesh
    axes the gather must state its output sharding; under Auto axes the
    partitioner propagates it.  Both give the unsharded lookup."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.models import layers as L
    cfg = get_config("qwen2-0.5b", reduced=True)
    p, spec = L.init_embedding(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(3, 4) % cfg.vocab_size
    want = np.asarray(p["tok"].astype(jnp.dtype(cfg.dtype))[tokens])
    kind = AxisType.Explicit if explicit else AxisType.Auto
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(kind,) * 2)
    sharded = {k: jax.device_put(v, NamedSharding(mesh, spec[k]))
               for k, v in p.items()}
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
    with jax.set_mesh(mesh):
        got = jax.jit(lambda q, t: L.embed(q, t, cfg))(sharded, tokens)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_dryrun_sweep_results_have_no_errors():
    """If the full 80-cell sweep has been run, every cell must be ok or an
    explicitly documented skip."""
    res_dir = REPO / "results" / "dryrun"
    if not res_dir.exists():
        pytest.skip("full sweep not run in this environment")
    recs = [json.loads(p.read_text()) for p in res_dir.glob("*.json")]
    assert len(recs) >= 80
    bad = [(r["arch"], r["shape"], r["mesh"]) for r in recs
           if r["status"] not in ("ok", "skipped")]
    assert not bad, f"dry-run failures: {bad}"
    skips = [r for r in recs if r["status"] == "skipped"]
    assert all("full-attention" in r["reason"] for r in skips)


# ----------------------------------------------------------------------
# ServeLoop lifecycle: the continuous-batching loop as an object
# ----------------------------------------------------------------------
def _serve_loop(requests, batch=2, gen=6, seed=0):
    import jax
    from repro.configs import get_config
    from repro.launch.serve import ServeLoop
    from repro.models import get_api
    cfg = get_config("qwen2-0.5b", reduced=True)
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(seed))
    loop = ServeLoop(api, cfg, params, batch=batch, prompt_len=8,
                     gen=gen, seed=seed)
    rng = np.random.default_rng(seed)
    for r in range(requests):
        loop.submit(r, rng.integers(1, cfg.vocab_size, size=8))
    return loop


def test_serve_loop_metrics_under_concurrent_clients():
    """Queue-depth gauge and request-latency histogram track the five
    clients through admission, refill, and completion."""
    from repro.obs import metrics
    metrics.reset()
    loop = _serve_loop(5, batch=2)
    depth = metrics.gauge("serve.queue_depth")
    assert depth.value == 5          # all five queued before the wave
    loop.start()
    assert depth.value == 3 and loop.active == 2
    loop.drain()
    assert depth.value == 0 and loop.pending == 0
    assert depth.max == 5
    snap = metrics.snapshot()
    lat = snap["serve.request_latency_s"]
    assert lat["count"] == loop.served >= 4
    assert loop.latencies and min(loop.latencies) > 0
    # later submissions waited in the queue at least as long
    assert max(loop.latencies) >= min(loop.latencies)
    assert snap["serve.tokens"]["value"] == sum(
        len(v) for v in loop.outputs.values())


def test_serve_loop_cancellation_mid_batch():
    """A queued request cancels instantly; a decoding request frees its
    slot at the next step (refilled from the queue, no latency row)."""
    from repro.obs import metrics
    metrics.reset()
    loop = _serve_loop(4, batch=2, gen=6)
    assert loop.cancel(3)            # still queued: dropped outright
    loop.start()
    assert loop.step()
    assert loop.cancel(0)            # mid-batch: slot frees next step
    assert not loop.cancel(99)       # unknown
    loop.drain()
    assert len(loop.outputs[0]) < 6      # partial output kept
    assert len(loop.outputs[3]) == 0     # never admitted
    assert len(loop.outputs[1]) == len(loop.outputs[2]) == 6
    assert loop.served == 2
    snap = metrics.snapshot()
    assert snap["serve.request_latency_s"]["count"] == 2
    assert not loop.cancel(1)        # already finished


def test_serve_loop_shutdown_drains_in_flight():
    """shutdown(drain=True) finishes the admitted slots and refuses new
    work; queued-but-unstarted requests stay unserved."""
    loop = _serve_loop(6, batch=2, gen=6)
    loop.start()
    assert loop.step()
    loop.shutdown(drain=True)
    assert loop.served == 2 and loop.active == 0
    assert len(loop.outputs[0]) == len(loop.outputs[1]) == 6
    assert loop.pending == 4         # never admitted after close
    assert all(len(loop.outputs[r]) == 0 for r in range(2, 6))
    with pytest.raises(RuntimeError):
        loop.submit(7, np.ones(8, np.int32))


def test_serve_loop_shutdown_abandons_without_drain():
    loop = _serve_loop(3, batch=2, gen=6)
    loop.start()
    assert loop.step()
    loop.shutdown(drain=False)
    assert loop.active == 0 and loop.served == 0
    assert not loop.step()           # idle and closed
    assert all(len(v) <= 1 for v in loop.outputs.values())
