"""Asynchronous trainer fleet (training/fleet/): ownership layout ==
the in-mesh owner-shard rule, pickle-free wire codec, quorum/staleness
apply semantics, the thread-driven 2-worker integration (real HTTP peer
plane, real jitted shard applies), v2 owner-part checkpoint bitwise
round trip + sync-loop resume, the grad-push fault drill, the fleet
alert rules, the worker-labeled Prometheus families, and the
``telemetry top`` per-worker columns. The subprocess drills (SIGKILL
recovery, CLI fleet, bounded-staleness convergence) are slow-marked —
``make train-fleet`` runs them.
"""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.training.fleet.ownership import (
    OwnershipLayout,
    local_opt_from_canonical,
    opt_part_records,
    shard_axis,
)
from spacy_ray_tpu.training.fleet.peer import (
    FleetCounters,
    OwnerState,
    PeerServer,
)
from spacy_ray_tpu.training.fleet.wire import (
    WireError,
    decode_arrays,
    encode_arrays,
)
from spacy_ray_tpu.util import write_synth_jsonl


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _free_base_port(n):
    """A base port for a CLI fleet, whose worker k binds ``base + k``
    seconds later, once its process is up. ``_free_ports`` cannot give
    one: it answers for a single port, and the kernel hands a port it has
    just seen closed to the next ``bind(0)`` of any test running beside
    this one. So the run of ``n`` ports is taken BELOW the range the
    kernel draws from (``ip_local_port_range``: 32768-60999), where only a
    process that asks for the number can sit, and every one of them is
    bound once to see that nobody does."""
    start = 21000 + (os.getpid() * 64) % 10000
    for base in range(start, start + 10000, 64):
        socks = []
        try:
            for k in range(n):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", base + k))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError(f"no run of {n} free ports from {start}")


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("fleet_data")
    write_synth_jsonl(d / "train.jsonl", 120, kind="tagger", seed=0)
    write_synth_jsonl(d / "dev.jsonl", 30, kind="tagger", seed=1)
    return d


def _config(tagger_config_text, data_dir, **over):
    cfg = Config.from_str(tagger_config_text)
    return cfg.apply_overrides(
        {
            "paths.train": str(data_dir / "train.jsonl"),
            "paths.dev": str(data_dir / "dev.jsonl"),
            **over,
        }
    )


def _run_thread_fleet(
    cfg, out, n, *, quorum=0, staleness=0, metrics_dir=None, timeout=300,
    fault_plan=None, **worker_kw
):
    """Drive N fleet workers as threads in this process — real HTTP peer
    servers on loopback, real jitted grad/apply, no subprocess spawn
    cost. Returns {worker_id: TrainResult}."""
    from spacy_ray_tpu.training import resilience
    from spacy_ray_tpu.training.fleet.worker import train_fleet_worker

    ports = _free_ports(n)
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    results, errors = {}, {}
    prev_plan = resilience.set_fault_plan(fault_plan)

    def run(k):
        try:
            _, res = train_fleet_worker(
                cfg, out, worker_id=k, n_workers=n, quorum=quorum,
                max_staleness=staleness, port=ports[k], peer_urls=urls,
                stdout_log=False, install_signal_handlers=False,
                metrics_dir=metrics_dir, quorum_wait_s=60.0, **worker_kw,
            )
            results[k] = res
        except Exception as e:  # surfaced via the errors dict
            errors[k] = e

    threads = [
        threading.Thread(target=run, args=(k,), name=f"fleet-test-{k}")
        for k in range(n)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout)
        alive = [t.name for t in threads if t.is_alive()]
        assert not alive, f"fleet workers wedged: {alive}"
        assert not errors, f"fleet workers raised: {errors}"
    finally:
        resilience.set_fault_plan(prev_plan)
    return results


# ----------------------------------------------------------------------
# Ownership layout
# ----------------------------------------------------------------------


def test_shard_axis_matches_zero1_spec(mesh8):
    """The host-side rule IS the in-mesh owner-shard rule: for every
    shape, the axis the fleet shards on equals the axis zero1_spec puts
    the 'data' axis on (or both replicate)."""
    import jax.numpy as jnp

    from spacy_ray_tpu.parallel.mesh import zero1_spec

    shapes = [(16,), (16, 8), (3, 16), (7,), (5, 3), (8, 24, 4), ()]
    for shape in shapes:
        leaf = jnp.zeros(shape)
        spec = zero1_spec(leaf, mesh8).spec
        mesh_axis = next(
            (i for i, s in enumerate(spec) if s == "data"), None
        )
        assert shard_axis(shape, 8) == mesh_axis, shape


def test_layout_slice_merge_roundtrip():
    rng = np.random.default_rng(0)
    template = {
        "a": {"W": rng.random((8, 6), dtype=np.float32),
              "b": rng.random(3, dtype=np.float32)},
        "c": {"E": rng.random((10, 4), dtype=np.float32)},
    }
    layout = OwnershipLayout(template, 2)
    # unshardable leaf (3,) belongs to worker 0 only
    assert "a/b" in layout.owned_keys(0)
    assert "a/b" not in layout.owned_keys(1)
    # every worker owns a slice of every shardable leaf
    for w in (0, 1):
        assert "a/W" in layout.owned_keys(w)
        assert "c/E" in layout.owned_keys(w)
    # merging every worker's slices into zeros reconstructs the tree
    import jax

    zeros = jax.tree_util.tree_map(np.zeros_like, template)
    for w in (0, 1):
        layout.merge_flat(zeros, w, layout.flat_slices(template, w))
    for path in ("a", "c"):
        for leaf in template[path]:
            np.testing.assert_array_equal(
                zeros[path][leaf], template[path][leaf]
            )


def test_path_scheme_matches_checkpoint_flatten():
    """The fleet's leaf walk and the checkpoint's _flatten must agree on
    keys forever — fleet part files and params-npz interoperate through
    that path scheme."""
    from spacy_ray_tpu.training.checkpoint import _flatten, _unflatten
    from spacy_ray_tpu.training.fleet.ownership import (
        iter_leaves,
        path_key,
        tree_from_flat,
    )

    tree = {
        "b": {"inner": {"W": np.ones((2, 2), np.float32)}},
        "a": {"x": np.zeros(3, np.float32)},
    }
    fleet_keys = [path_key(p) for p, _ in iter_leaves(tree)]
    assert fleet_keys == list(_flatten(tree).keys())
    flat = {k: v for (p, v), k in zip(iter_leaves(tree), fleet_keys)}
    import jax

    assert jax.tree_util.tree_structure(
        tree_from_flat(flat)
    ) == jax.tree_util.tree_structure(_unflatten(flat))


def test_layout_signature_depends_on_workers_and_shapes():
    t = {"a": np.zeros((8, 4), np.float32)}
    assert OwnershipLayout(t, 2).signature() != OwnershipLayout(t, 4).signature()
    t2 = {"a": np.zeros((8, 5), np.float32)}
    assert OwnershipLayout(t, 2).signature() != OwnershipLayout(t2, 2).signature()


# ----------------------------------------------------------------------
# Wire codec
# ----------------------------------------------------------------------


def test_wire_roundtrip():
    arrays = {
        "a/W": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b": np.array(3.5, dtype=np.float64),
        "c": np.zeros((0, 4), dtype=np.int32),
    }
    body = encode_arrays({"worker": 1, "stamp": 7}, arrays)
    meta, out = decode_arrays(body)
    assert meta == {"worker": 1, "stamp": 7}
    assert set(out) == set(arrays)
    for k in arrays:
        np.testing.assert_array_equal(out[k], arrays[k])
        assert out[k].dtype == arrays[k].dtype


def test_wire_rejects_malformed():
    good = encode_arrays({"v": 1}, {"x": np.ones(4, np.float32)})
    with pytest.raises(WireError):
        decode_arrays(b"NOPE" + good[4:])
    with pytest.raises(WireError):
        decode_arrays(good[:-3])  # truncated data
    with pytest.raises(WireError):
        decode_arrays(good + b"xx")  # trailing bytes


# ----------------------------------------------------------------------
# Owner quorum / staleness semantics (pure, fake apply)
# ----------------------------------------------------------------------


def _fake_owner(quorum, staleness, n=3):
    applied = []

    def apply_fn(params, opt_state, grads):
        applied.append(grads)
        return (
            {"x": params["x"] + grads["x"]},
            opt_state,
        )

    owner = OwnerState(
        worker_id=0, n_workers=n, quorum=quorum, max_staleness=staleness,
        apply_fn=apply_fn,
        slice_params={"x": np.zeros(4, np.float32)},
        opt_state={"count": 0},
        counters=FleetCounters(),
    )
    return owner, applied


def test_owner_applies_at_quorum_and_bumps_version():
    owner, applied = _fake_owner(quorum=2, staleness=0)
    g = {"x": np.ones(4, np.float32)}
    ok, v = owner.submit(1, 0, g)
    assert ok and v == 0 and not applied
    ok, v = owner.submit(2, 0, g)
    assert ok and v == 1 and len(applied) == 1
    # the applied gradient is the MEAN over the quorum
    np.testing.assert_allclose(applied[0]["x"], np.ones(4))
    snap = owner.counters.snapshot()
    assert snap["grad_applied"] == 2 and snap["applies"] == 1


def test_owner_discards_stale_and_future_stamps():
    owner, applied = _fake_owner(quorum=1, staleness=0)
    g = {"x": np.ones(4, np.float32)}
    assert owner.submit(1, 0, g)[0]  # applies instantly at quorum 1
    assert owner.version == 1
    ok, _ = owner.submit(2, 0, g)  # one behind at S=0: discarded
    assert not ok
    ok, _ = owner.submit(2, 5, g)  # FUTURE stamp (pre-crash cache): discarded
    assert not ok
    snap = owner.counters.snapshot()
    assert snap["grad_discarded"] == 2


def test_owner_bounded_staleness_accepts_lagged():
    owner, applied = _fake_owner(quorum=1, staleness=2)
    g = {"x": np.ones(4, np.float32)}
    owner.submit(1, 0, g)
    owner.submit(1, 1, g)
    assert owner.version == 2
    ok, _ = owner.submit(2, 0, g)  # lag 2 <= S=2: accepted (and applied)
    assert ok and owner.version == 3
    ok, _ = owner.submit(2, 0, g)  # lag 3 > S: discarded
    assert not ok


def test_owner_rejects_structural_mismatch_and_bogus_sender():
    """Wire-valid but wrong-shaped/keyed payloads (a peer on a different
    config) and out-of-range sender ids are counted discards — they must
    never enter the quorum buffer where they would wedge the next
    apply."""
    owner, applied = _fake_owner(quorum=2, staleness=0)
    good = {"x": np.ones(4, np.float32)}
    assert not owner.submit(1, 0, {"y": np.ones(4, np.float32)})[0]
    assert not owner.submit(1, 0, {"x": np.ones(5, np.float32)})[0]
    assert not owner.submit(99, 0, good)[0]  # bogus quorum sender
    assert owner.counters.snapshot()["grad_discarded"] == 3
    # the shard still works: a legitimate quorum applies
    owner.submit(1, 0, good)
    owner.submit(2, 0, good)
    assert owner.version == 1 and len(applied) == 1


def test_owner_apply_failure_drops_round_not_shard():
    """If the apply itself raises, the buffered round is dropped and
    counted — the poisoned buffer must not re-raise at every future
    quorum and freeze the shard version forever."""
    calls = {"n": 0}

    def apply_fn(params, opt_state, grads):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return {"x": params["x"] + grads["x"]}, opt_state

    owner = OwnerState(
        worker_id=0, n_workers=3, quorum=2, max_staleness=0,
        apply_fn=apply_fn,
        slice_params={"x": np.zeros(4, np.float32)},
        opt_state={}, counters=FleetCounters(),
    )
    g = {"x": np.ones(4, np.float32)}
    owner.submit(1, 0, g)
    owner.submit(2, 0, g)  # first apply raises: round dropped, counted
    assert owner.version == 0
    assert owner.counters.snapshot()["grad_discarded"] == 2
    owner.submit(1, 0, g)
    owner.submit(2, 0, g)  # shard still serves: next quorum applies
    assert owner.version == 1


def test_owner_wait_version_above():
    owner, _ = _fake_owner(quorum=1, staleness=0)
    assert not owner.wait_version_above(0, timeout=0.05)
    owner.submit(1, 0, {"x": np.ones(4, np.float32)})
    assert owner.wait_version_above(0, timeout=0.05)


# ----------------------------------------------------------------------
# Opt-state owner parts: bitwise round trip through the v2 format
# ----------------------------------------------------------------------


def test_opt_parts_bitwise_roundtrip(tmp_path):
    """Parts written by N 'processes' (one writer call per owner)
    reassemble through the UNCHANGED v2 reader into the canonical
    state, and carving each owner's local state back out of it is
    BITWISE identical — the elastic cross-process resume contract."""
    import jax
    import jax.numpy as jnp

    from spacy_ray_tpu.parallel.step import make_shard_apply
    from spacy_ray_tpu.registry import registry
    from spacy_ray_tpu.training.checkpoint import _assemble_opt_parts

    rng = np.random.default_rng(1)
    template = {
        "m": {"W": rng.random((8, 6), dtype=np.float32),
              "b": rng.random(3, dtype=np.float32)},
        "n": {"E": rng.random((10, 4), dtype=np.float32)},
    }
    n_workers = 2
    layout = OwnershipLayout(template, n_workers)
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
    apply_fn = make_shard_apply(tx, donate=False)

    locals_, files, digests = {}, [], {}
    for w in range(n_workers):
        slices = jax.tree_util.tree_map(
            jnp.asarray, layout.slice_tree(template, w)
        )
        state = tx.init(slices)
        params = slices
        for i in range(3):  # move the state off its init values
            grads = jax.tree_util.tree_map(
                lambda x: jnp.asarray(
                    np.full(x.shape, 0.01 * (i + 1), np.float32)
                ),
                slices,
            )
            params, state = apply_fn(params, state, grads)
        locals_[w] = state
        n_leaves, skeleton, records = opt_part_records(
            tx, template, layout, state, w
        )
        from spacy_ray_tpu.training.checkpoint import write_fleet_opt_part

        digests[w] = write_fleet_opt_part(
            tmp_path, stamp=3, part=w, parts=n_workers,
            n_leaves=n_leaves, records=records,
            skeleton=skeleton if w == 0 else None,
        )
        files.append(tmp_path / f"opt_state-3.part{w}of{n_workers}.pkl")

    canonical = _assemble_opt_parts(files)
    # same structure as a single-process init over the full tree
    want_struct = jax.tree_util.tree_structure(
        jax.eval_shape(tx.init, template)
    )
    assert jax.tree_util.tree_structure(canonical) == want_struct
    for w in range(n_workers):
        slices_np = layout.slice_tree(template, w)
        back = local_opt_from_canonical(tx, layout, canonical, w, slices_np)
        for a, b in zip(
            jax.tree_util.tree_leaves(locals_[w]),
            jax.tree_util.tree_leaves(back),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# Peer server surface (no telemetry: ledger-only /metrics)
# ----------------------------------------------------------------------


def test_peer_server_metrics_and_params():
    import urllib.request

    counters = FleetCounters()
    owner = OwnerState(
        worker_id=1, n_workers=2, quorum=1, max_staleness=0,
        apply_fn=lambda p, o, g: ({"x": p["x"] + g["x"]}, o),
        slice_params={"x": np.zeros(4, np.float32)},
        opt_state={}, counters=counters,
    )
    server = PeerServer(
        owner, worker_id=1, layout_signature="sig", counters=counters,
    )
    host, port = server.start()
    try:
        with urllib.request.urlopen(
            f"http://{host}:{port}/healthz", timeout=5
        ) as r:
            h = json.loads(r.read())
        assert h["role"] == "fleet-worker" and h["worker"] == 1
        assert h["layout"] == "sig" and h["version"] == 0
        # grad push over real HTTP bumps the version at quorum 1
        body = encode_arrays(
            {"worker": 0, "stamp": 0}, {"x": np.ones(4, np.float32)}
        )
        req = urllib.request.Request(
            f"http://{host}:{port}/grad", data=body, method="POST"
        )
        with urllib.request.urlopen(req, timeout=5) as r:
            reply = json.loads(r.read())
        assert reply == {"accepted": True, "version": 1}
        # stale push is typed-refused and counted
        req = urllib.request.Request(
            f"http://{host}:{port}/grad", data=body, method="POST"
        )
        with urllib.request.urlopen(req, timeout=5) as r:
            assert json.loads(r.read())["accepted"] is False
        # version-gated pull: 200 with bytes, then 204 when current
        with urllib.request.urlopen(
            f"http://{host}:{port}/params?known=0", timeout=5
        ) as r:
            meta, arrays = decode_arrays(r.read())
        assert meta["version"] == 1
        np.testing.assert_allclose(arrays["x"], np.ones(4))
        with urllib.request.urlopen(
            f"http://{host}:{port}/params?known=1", timeout=5
        ) as r:
            assert r.status == 204
            assert r.headers["X-SRT-Version"] == "1"
        # malformed query = clean 400, not a handler traceback
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://{host}:{port}/params?known=abc", timeout=5
            )
        assert ei.value.code == 400
        # telemetry-off /metrics still serves the ledger, and the
        # Prometheus form carries the worker label on every family
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5
        ) as r:
            snap = json.loads(r.read())
        assert snap["counters"]["grad_discarded"] == 1
        assert snap["gauges"]["param_version"] == 1
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics?format=prometheus", timeout=5
        ) as r:
            text = r.read().decode("utf8")
        assert 'srt_training_grad_received_total{worker="1"} 2' in text
        assert 'srt_training_grad_discarded_total{worker="1"} 1' in text
        assert 'srt_training_param_version{worker="1"} 1' in text
    finally:
        server.stop()


# ----------------------------------------------------------------------
# Thread-fleet integration: trains, checkpoints, resumes into sync
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet_run(tagger_config_text, data_dir, tmp_path_factory):
    """ONE 2-worker fleet training run (S=0, quorum=2 — the
    synchronous-equivalent point), shared by the integration tests."""
    out = tmp_path_factory.mktemp("fleet_out")
    cfg = _config(
        tagger_config_text, data_dir,
        **{"training.max_steps": 12, "training.eval_frequency": 6},
    )
    results = _run_thread_fleet(
        cfg, out, 2, quorum=2, staleness=0,
        metrics_dir=out / "metrics",
    )
    return out, results


def test_fleet_trains_and_learns(fleet_run):
    out, results = fleet_run
    assert set(results) == {0, 1}
    r0 = results[0]
    assert r0.final_step == 12
    assert r0.best_score > 0.8, f"fleet failed to learn: {r0.best_score}"
    for k, r in results.items():
        fl = r.fleet
        assert fl["version"] == 12  # lockstep at S=0, quorum=N
        assert fl["counters"]["grad_discarded"] == 0
        assert fl["counters"]["push_failed"] == 0
        assert fl["counters"]["apply_wait_timeouts"] == 0
        # conservation: everything received was applied or discarded
        # (nothing pending at the quiescent end)
        assert (
            fl["counters"]["grad_applied"]
            + fl["counters"]["grad_discarded"]
            == fl["counters"]["grad_received"]
        )
        # per-phase accounting exists and is positive where it must be
        assert fl["phases"]["grad"] > 0
        assert fl["phases"]["push"] >= 0
    # per-worker ledgers + telemetry files (the CI failure artifacts)
    for k in (0, 1):
        ledger = json.loads(
            (out / f"fleet-worker-{k}.json").read_text("utf8")
        )
        assert ledger["counters"]["grad_discarded"] == 0
        assert (out / "metrics" / f"fleet-worker-{k}" / "metrics.jsonl").exists()


def test_fleet_checkpoint_is_v2_owner_parts(fleet_run):
    out, _ = fleet_run
    last = out / "last-model"
    meta = json.loads((last / "train_meta.json").read_text("utf8"))
    assert meta["format"] == 2
    assert meta["opt_shards"] == 2
    assert (last / "opt_state-12.part0of2.pkl").exists()
    assert (last / "opt_state-12.part1of2.pkl").exists()
    fleet_extra = meta["extra"]["fleet"]
    assert fleet_extra["n_workers"] == 2
    assert fleet_extra["versions"] == [12, 12]


def test_fleet_checkpoint_resumes_into_sync_loop(fleet_run, tagger_config_text, data_dir):
    """The elastic cross-process proof: per-owner parts written by the
    N fleet workers load through the UNCHANGED v2 reader and the
    single-process synchronous loop resumes from them."""
    import jax

    from spacy_ray_tpu.training.checkpoint import TrainCheckpoint
    from spacy_ray_tpu.training.loop import train

    out, results = fleet_run
    state = TrainCheckpoint.load(out / "last-model")
    assert state["step"] == 12
    # every optimizer leaf assembled (no holes): finite and shaped
    for leaf in jax.tree_util.tree_leaves(state["opt_state"]):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float64)))
    cfg = _config(
        tagger_config_text, data_dir,
        **{"training.max_steps": 18, "training.eval_frequency": 6},
    )
    _, res = train(
        cfg, output_path=out, n_workers=1, resume=True, stdout_log=False
    )
    assert res.final_step == 18  # resumed at 12, ran 6 synchronous steps
    assert res.best_score > 0.8


def test_peers_follow_the_lead_workers_finalize(
    tagger_config_text, data_dir, tmp_path
):
    """When the lead stops early (patience/max_steps) and finalizes,
    peers stop instead of training headless to their own max_steps —
    un-checkpointable progress (only worker 0 commits) would be wasted
    compute."""
    import threading as _threading

    from spacy_ray_tpu.training.fleet.worker import train_fleet_worker

    cfg = _config(
        tagger_config_text, data_dir,
        **{"training.max_steps": 400, "training.eval_frequency": 4},
    )
    ports = _free_ports(2)
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    results, errors = {}, {}

    def run(k, max_steps):
        try:
            _, res = train_fleet_worker(
                cfg, tmp_path / "out", worker_id=k, n_workers=2,
                quorum=1, max_staleness=1, port=ports[k], peer_urls=urls,
                stdout_log=False, install_signal_handlers=False,
                max_steps_override=max_steps, quorum_wait_s=30.0,
            )
            results[k] = res
        except Exception as e:
            errors[k] = e

    threads = [
        _threading.Thread(target=run, args=(0, 6)),
        _threading.Thread(target=run, args=(1, 400)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errors, errors
    assert results[0].final_step == 6
    # worker 1 stopped shortly after the lead finalized, far short of 400
    assert results[1].final_step < 100, results[1].final_step


def test_fleet_grad_push_fault_drill(tagger_config_text, data_dir, tmp_path):
    """FaultPlan 'grad-push' site: an injected OSError on the first push
    exhausts the bounded retry, is counted as push_failed, and the fleet
    keeps training (fire-and-forget = lost-RPC drill)."""
    from spacy_ray_tpu.training.resilience import FaultPlan

    cfg = _config(
        tagger_config_text, data_dir,
        **{"training.max_steps": 4, "training.eval_frequency": 4},
    )
    results = _run_thread_fleet(
        cfg, tmp_path / "out", 2, quorum=1, staleness=1,
        fault_plan=FaultPlan([("grad-push", 1, "oserror"),
                              ("grad-push", 2, "oserror")]),
        push_retries=0,
    )
    total_failed = sum(
        r.fleet["counters"]["push_failed"] for r in results.values()
    )
    assert total_failed >= 1
    for r in results.values():
        assert r.final_step == 4


# ----------------------------------------------------------------------
# Alert rules + top columns + prometheus labels
# ----------------------------------------------------------------------


def test_default_training_fleet_rules_fire():
    from spacy_ray_tpu.alerting import AlertEngine, default_training_rules

    class FakeClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    clock = FakeClock()
    rules = default_training_rules(fleet=True)
    names = {r.name for r in rules}
    assert {"fleet-grad-push-stalled", "fleet-discard-burn"} <= names
    eng = AlertEngine(rules, clock=clock, source="trainer")

    def snap(pushed, received, discarded, steps):
        return {"counters": {
            "grad_pushed": pushed, "grad_received": received,
            "grad_discarded": discarded, "steps": steps,
        }}

    # healthy fleet: pushes move, discards ~0 — nothing fires
    for i in range(40):
        clock.t += 10.0
        eng.evaluate(snap(i * 4, i * 4, 0, i))
    states = {s["alert"]: s for s in eng.states()}
    assert states["fleet-grad-push-stalled"]["state"] == "inactive"
    assert states["fleet-discard-burn"]["state"] == "inactive"
    # push counter freezes while steps keep moving: the wedged-peer page
    for i in range(40, 60):
        clock.t += 10.0
        eng.evaluate(snap(160, 160, 0, i))
    states = {s["alert"]: s for s in eng.states()}
    assert states["fleet-grad-push-stalled"]["state"] == "firing"
    # discard burn: >30% of received discarded inside the window
    eng2 = AlertEngine(
        default_training_rules(fleet=True), clock=clock, source="trainer"
    )
    base = clock.t
    for i in range(40):
        clock.t = base + (i + 1) * 10.0
        eng2.evaluate(snap(i * 10, i * 10, i * 5, i))  # 50% discard rate
    states = {s["alert"]: s for s in eng2.states()}
    assert states["fleet-discard-burn"]["state"] == "firing"


def test_push_stalled_rule_stays_silent_without_peer_pushes():
    """A topology that never pushes to peers (fleet of one; peers that
    own nothing) exports grad_pushed frozen at 0 — the arm_above gate
    keeps the push-stalled page silent until the counter has EVER
    moved."""
    from spacy_ray_tpu.alerting import AlertEngine, default_training_rules

    class FakeClock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = FakeClock()
    eng = AlertEngine(
        default_training_rules(fleet=True), clock=clock, source="trainer"
    )
    for i in range(60):  # 600s of a healthy fleet-of-one: zero forever
        clock.t += 10.0
        eng.evaluate({"counters": {"grad_pushed": 0, "steps": i}})
    states = {s["alert"]: s for s in eng.states()}
    assert states["fleet-grad-push-stalled"]["state"] == "inactive"


def test_top_classifies_ledger_only_fleet_worker_as_trainer():
    """A telemetry-off fleet worker serves only its ledger (counters +
    fleet_worker/param_version gauges, no histograms) — top must still
    render it as a trainer row, not an all-dash serving row."""
    from spacy_ray_tpu.top import TopModel, classify_payload, render

    payload = {
        "counters": {"grad_pushed": 10, "grad_received": 10,
                     "grad_discarded": 0},
        "gauges": {"fleet_worker": 2, "param_version": 5},
    }
    assert classify_payload(payload) == "trainer"
    row = TopModel().update("http://t:2", payload, now=1.0)
    assert row["kind"] == "trainer" and row["worker"] == 2
    assert "[fleet worker 2]" in render([row])


def test_top_renders_fleet_worker_columns():
    from spacy_ray_tpu.top import TopModel, render

    payload = {
        "counters": {"steps": 100, "words": 5000, "grad_pushed": 200,
                     "grad_received": 200, "grad_discarded": 20},
        "gauges": {"fleet_worker": 1, "param_version": 97},
        "histograms": {"step_seconds": {"p50": 0.01, "p95": 0.02}},
    }
    later = {
        "counters": {"steps": 110, "words": 5500, "grad_pushed": 220,
                     "grad_received": 220, "grad_discarded": 25},
        "gauges": {"fleet_worker": 1, "param_version": 107},
        "histograms": {"step_seconds": {"p50": 0.01, "p95": 0.02}},
    }
    model = TopModel()
    model.update("http://t:1", payload, now=100.0)
    row = model.update("http://t:1", later, now=110.0)
    assert row["kind"] == "trainer"
    assert row["worker"] == 1
    assert row["version"] == 107
    assert row["push_s"] == pytest.approx(2.0)
    assert row["discard_s"] == pytest.approx(0.5)
    assert row["discard_rate"] == pytest.approx(0.25)
    text = render([row])
    assert "[fleet worker 1]" in text
    assert "disc-rate 25%" in text


def test_fault_site_grad_push_registered():
    from spacy_ray_tpu.training.resilience import FAULT_SITES, FaultPlan

    assert "grad-push" in FAULT_SITES
    FaultPlan([("grad-push", 1, "oserror")])  # parses/validates


# ----------------------------------------------------------------------
# Subprocess drills (slow tier; `make train-fleet`)
# ----------------------------------------------------------------------


def _fleet_cli_cmd(cfg_path, data_dir, out, n, *, steps, quorum, staleness,
                   base_port, extra=()):
    import sys

    return [
        sys.executable, "-m", "spacy_ray_tpu", "train", str(cfg_path),
        "--device", "cpu",
        "--fleet-workers", str(n),
        "--quorum", str(quorum),
        "--max-staleness", str(staleness),
        "--fleet-base-port", str(base_port),
        "--output", str(out),
        f"--paths.train={data_dir / 'train.jsonl'}",
        f"--paths.dev={data_dir / 'dev.jsonl'}",
        f"--training.max_steps={steps}",
        "--training.eval_frequency=4",
        *extra,
    ]


def test_fleet_obs_acceptance_subprocess_trace_and_report(
    tagger_config_text, data_dir, tmp_path
):
    """The PR 15 acceptance run: a REAL 2-worker fleet (coordinator + 2
    worker subprocesses over the CLI, telemetry on). Mid-run,
    ``telemetry collect-trace --fleet-base-port N --workers 2`` merges
    both workers' live buffers into ONE Perfetto file with spans on two
    distinct process tracks — including a grad_push span on one track
    and an owner-side grad_apply span on the other. After the clean
    exit, ``telemetry summarize <run-dir>`` digests the fleet layout and
    ``telemetry report`` renders per-worker loss trajectories, the
    phase-share table, and a non-empty staleness histogram."""
    import subprocess
    import urllib.request

    from spacy_ray_tpu.cli import telemetry_command
    from spacy_ray_tpu.training.report import build_run_report
    from spacy_ray_tpu.training.telemetry import summarize_metrics

    cfg_path = tmp_path / "cfg.cfg"
    cfg_path.write_text(tagger_config_text, encoding="utf8")
    out = tmp_path / "out"
    base_port = _free_base_port(2)
    cmd = _fleet_cli_cmd(
        cfg_path, data_dir, out, 2, steps=16, quorum=2, staleness=1,
        base_port=base_port,
        extra=("--metrics-dir", str(out / "metrics")),
    )
    coord = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True
    )
    trace_path = tmp_path / "fleet-trace.json"
    try:
        # wait until BOTH workers are up and have stepped at least twice
        # (>= 1 push and >= 1 apply each at quorum 2), then collect the
        # live buffers through the real CLI path
        deadline = time.monotonic() + 420
        ready = set()
        while time.monotonic() < deadline and len(ready) < 2:
            for k in (0, 1):
                if k in ready:
                    continue
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{base_port + k}/metrics",
                        timeout=2,
                    ) as r:
                        payload = json.loads(r.read())
                except (OSError, ValueError):
                    continue
                if (payload.get("counters") or {}).get("steps", 0) >= 2:
                    ready.add(k)
            if len(ready) < 2:
                assert coord.poll() is None, (
                    "fleet exited before both workers were scrapable: "
                    + coord.stderr.read()[-2000:]
                )
                time.sleep(0.2)
        assert len(ready) == 2, "workers never reached step 2"
        rc = telemetry_command([
            "collect-trace",
            "--fleet-base-port", str(base_port),
            "--workers", "2",
            "--out", str(trace_path),
        ])
        assert rc == 0
        coord_rc = coord.wait(timeout=600)
        assert coord_rc == 0, coord.stderr.read()[-2000:]
    finally:
        if coord.poll() is None:
            coord.kill()
            coord.wait(timeout=30)
    # ONE merged Perfetto file, >= 2 distinct worker process tracks
    merged = json.loads(trace_path.read_text("utf8"))
    tracks = {
        e["pid"]: (e.get("args") or {}).get("name")
        for e in merged["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert len(tracks) >= 2, tracks
    assert all("fleet-worker" in (n or "") for n in tracks.values())
    spans = [
        (e.get("pid"), e.get("name"))
        for e in merged["traceEvents"] if e.get("ph") == "X"
    ]
    push_pids = {p for p, n in spans if n == "grad_push"}
    apply_pids = {p for p, n in spans if n == "grad_apply"}
    assert push_pids and apply_pids
    # a push leaving one worker and an apply landing on ANOTHER track
    assert any(
        pp != ap for pp in push_pids for ap in apply_pids
    ), (push_pids, apply_pids)
    # the fleet-aware offline surfaces on the finished run dir
    summary = summarize_metrics(out)
    assert "workers: 2" in summary
    assert "trainer fleet: 2 worker(s)" in summary
    report = build_run_report(out)
    assert "## Per-worker loss trajectories" in report
    assert "- worker 0" in report and "- worker 1" in report
    assert "## Phase share" in report
    assert "## Staleness histogram" in report
    (tmp_path / "run-report.md").write_text(report, encoding="utf8")


def test_fleet_divergence_drill_fires_alert_and_bundle(
    tagger_config_text, data_dir, tmp_path
):
    """Forced-divergence drill: a FaultPlan NaN poisons ONE worker's
    per-step loss mid-run. The lead's convergence watch flags that
    worker (mode "nan"), the fleet-worker-diverging alert fires, and an
    incident bundle naming the worker lands in the incidents dir."""
    from spacy_ray_tpu.training.resilience import FaultPlan

    out = tmp_path / "out"
    incidents = tmp_path / "incidents"
    cfg = _config(
        tagger_config_text, data_dir,
        **{
            "training.max_steps": 24,
            # no mid-run eval: the drill isolates the WATCH chain (the
            # eval-boundary nan-loss detector is PR 3's, already tested)
            "training.eval_frequency": 50,
            "training.incident_dir": str(incidents),
        },
    )
    results = _run_thread_fleet(
        cfg, out, 2, quorum=1, staleness=1,
        metrics_dir=out / "metrics",
        fault_plan=FaultPlan([("step", 6, "nan")]),
        watch_interval_s=0.2, alert_interval_s=0.2,
    )
    assert set(results) == {0, 1}
    lead_rows = [
        json.loads(l)
        for l in (out / "metrics" / "fleet-worker-0" / "metrics.jsonl")
        .read_text("utf8").splitlines()
    ]
    flags = [
        r for r in lead_rows
        if r.get("kind") == "anomaly"
        and r.get("anomaly") == "fleet-divergence"
    ]
    assert flags, "the divergence watch never flagged the NaN worker"
    named = int(flags[0]["worker"])
    assert flags[0]["mode"] == "nan"
    assert f"worker {named}" in flags[0]["message"]
    # the named worker really is the one that trained on the NaN
    named_rows = [
        json.loads(l)
        for l in (
            out / "metrics" / f"fleet-worker-{named}" / "metrics.jsonl"
        ).read_text("utf8").splitlines()
    ]
    assert any(
        r.get("kind") == "step" and r.get("loss") == "nan"
        for r in named_rows
    )
    # the alert fired on the lead's engine (alerts.jsonl transition row)
    alert_rows = [
        json.loads(l)
        for l in (out / "metrics" / "fleet-worker-0" / "alerts.jsonl")
        .read_text("utf8").splitlines()
    ]
    assert any(
        r.get("alert") == "fleet-worker-diverging"
        and r.get("to") == "firing"
        for r in alert_rows
    ), alert_rows
    # the incident bundle names the worker
    bundles = [
        d for d in incidents.iterdir()
        if d.is_dir() and "fleet-divergence" in d.name
    ]
    assert bundles, list(incidents.iterdir())
    inc = json.loads((bundles[0] / "incident.json").read_text("utf8"))
    assert inc["worker"] == named
    assert f"worker {named}" in inc["reason"]
    from spacy_ray_tpu.incidents import render_postmortem

    rendered = render_postmortem(bundles[0])
    assert f"worker={named}" in rendered


def test_fleet_obs_acceptance_zero_telemetry_guard(
    tagger_config_text, data_dir, tmp_path, monkeypatch
):
    """A fleet worker with telemetry off constructs NO observability
    objects — no registry, no trace buffer, no detectors, no alert
    engine, no recorder (booby-trapped constructors prove it) — while
    the ledger counters and the peer plane keep working."""
    from spacy_ray_tpu import alerting as alerting_mod
    from spacy_ray_tpu import incidents as incidents_mod
    from spacy_ray_tpu.training import telemetry as telemetry_mod

    def _boom(*a, **k):
        raise AssertionError(
            "telemetry constructed on the fleet's disabled path"
        )

    monkeypatch.setattr(telemetry_mod.Telemetry, "__init__", _boom)
    monkeypatch.setattr(telemetry_mod.MetricsRegistry, "__init__", _boom)
    monkeypatch.setattr(telemetry_mod.TraceBuffer, "__init__", _boom)
    monkeypatch.setattr(telemetry_mod.AnomalyDetectors, "__init__", _boom)
    monkeypatch.setattr(
        telemetry_mod.FleetDivergenceDetector, "__init__", _boom
    )
    monkeypatch.setattr(alerting_mod.AlertEngine, "__init__", _boom)
    monkeypatch.setattr(incidents_mod.FlightRecorder, "__init__", _boom)
    # PR 18: the host sampler obeys the same contract
    from spacy_ray_tpu.training import hoststats as hoststats_mod

    monkeypatch.setattr(hoststats_mod.ProcessSampler, "__init__", _boom)
    cfg = _config(
        tagger_config_text, data_dir,
        **{"training.max_steps": 3, "training.eval_frequency": 3},
    )
    results = _run_thread_fleet(
        cfg, tmp_path / "out", 2, quorum=1, staleness=1, metrics_dir=None
    )
    for r in results.values():
        assert r.final_step == 3
        assert r.fleet["counters"]["grad_received"] >= 1


@pytest.mark.slow
def test_fleet_cli_subprocess_run(tagger_config_text, data_dir, tmp_path):
    """The real thing: coordinator + 2 worker PROCESSES over the CLI;
    parts written by separate processes resume into the sync loop."""
    import subprocess

    from spacy_ray_tpu.training.checkpoint import TrainCheckpoint
    from spacy_ray_tpu.training.loop import train

    cfg_path = tmp_path / "cfg.cfg"
    cfg_path.write_text(tagger_config_text, encoding="utf8")
    out = tmp_path / "out"
    base_port = _free_base_port(2)
    proc = subprocess.run(
        _fleet_cli_cmd(cfg_path, data_dir, out, 2, steps=8, quorum=2,
                       staleness=0, base_port=base_port),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    for k in (0, 1):
        ledger = json.loads(
            (out / f"fleet-worker-{k}.json").read_text("utf8")
        )
        assert ledger["steps"] == 8
        assert ledger["counters"]["grad_discarded"] == 0
    state = TrainCheckpoint.load(out / "last-model")
    assert state["step"] == 8
    cfg = _config(
        tagger_config_text, data_dir, **{"training.max_steps": 12}
    )
    _, res = train(
        cfg, output_path=out, n_workers=1, resume=True, stdout_log=False
    )
    assert res.final_step == 12


@pytest.mark.slow
def test_fleet_sigkill_recovery(tagger_config_text, data_dir, tmp_path):
    """SIGKILL one non-lead worker mid-training: quorum keeps the fleet
    stepping, the supervisor restarts it with --resume, the rejoined
    lineage's stale traffic is discarded/counted, and the run finishes
    with a healthy score — zero NaN."""
    import signal
    import subprocess
    import urllib.request

    cfg_path = tmp_path / "cfg.cfg"
    cfg_path.write_text(tagger_config_text, encoding="utf8")
    out = tmp_path / "out"
    base_port = _free_base_port(2)
    # quorum=1: neither worker ever blocks on the other, so the fleet
    # keeps stepping through the kill; 40 steps keeps the survivor alive
    # well past the victim's ~20s restart (wait_for_peers at rejoin
    # needs the survivor's /healthz up)
    cmd = _fleet_cli_cmd(
        cfg_path, data_dir, out, 2, steps=40, quorum=1, staleness=1,
        base_port=base_port, extra=("--max-restarts", "2"),
    )
    coord = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True
    )
    victim_url = f"http://127.0.0.1:{base_port + 1}/healthz"

    def victim_version():
        try:
            with urllib.request.urlopen(victim_url, timeout=2) as r:
                return json.loads(r.read()).get("version")
        except OSError:
            return None

    try:
        # kill only after (a) the victim has applied a few versions and
        # (b) a fleet generation is COMMITTED — the restarted worker must
        # have something to --resume from for the rejoin path to be the
        # one under test
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            v = victim_version()
            if (
                v is not None
                and v >= 3
                and (out / "last-model" / "train_meta.json").exists()
            ):
                break
            time.sleep(0.5)
        else:
            pytest.fail(
                "victim never reached version 3 with a committed generation"
            )
        pid = int(
            subprocess.run(
                ["pgrep", "-f", "--", "--fleet-worker-id 1"],
                capture_output=True, text=True,
            ).stdout.split()[0]
        )
        import os as _os

        _os.kill(pid, signal.SIGKILL)
        # the supervisor must bring a NEW incarnation back onto the port
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if victim_version() is not None:
                break
            time.sleep(0.5)
        else:
            pytest.fail("victim worker never came back after SIGKILL")
        rc = coord.wait(timeout=600)
        assert rc == 0, (coord.stdout.read()[-2000:], coord.stderr.read()[-2000:])
    finally:
        if coord.poll() is None:
            coord.kill()
            coord.wait(timeout=30)
    ledger1 = json.loads((out / f"fleet-worker-1.json").read_text("utf8"))
    assert ledger1["resumed_from"] is not None  # rejoined via --resume
    ledger0 = json.loads((out / f"fleet-worker-0.json").read_text("utf8"))
    # the dead/restarted lineage shows up in the ledgers: lost RPCs
    # and/or version-mismatch discards, all COUNTED, none fatal
    disturbance = (
        ledger0["counters"]["push_failed"]
        + ledger0["counters"]["pull_failed"]
        + ledger0["counters"]["grad_discarded"]
        + ledger1["counters"]["grad_discarded"]
    )
    assert disturbance >= 1
    # zero NaN / score regression: the survivor's best model is healthy
    assert (out / "best-model" / "params.npz").exists()
    import numpy as _np

    with _np.load(out / "best-model" / "params.npz") as data:
        for name in data.files:
            assert _np.all(_np.isfinite(data[name])), name


@pytest.mark.slow
@pytest.mark.parametrize("staleness", [0, 1, 2])
def test_fleet_bounded_staleness_convergence(
    tagger_config_text, data_dir, tmp_path, staleness, sync_score_baseline
):
    """The acceptance gate: the async loop reaches the synchronous
    loop's score envelope on the fixture corpus at S∈{0,1,2}; the S=0
    run is score-equivalent to the synchronous loop."""
    cfg = _config(
        tagger_config_text, data_dir,
        **{"training.max_steps": 40, "training.eval_frequency": 10},
    )
    results = _run_thread_fleet(
        cfg, tmp_path / f"out-s{staleness}", 2, quorum=2,
        staleness=staleness, timeout=600,
    )
    fleet_score = results[0].best_score
    sync_score = sync_score_baseline
    assert fleet_score > 0.8, f"S={staleness}: failed to learn"
    assert fleet_score >= sync_score - 0.10, (
        f"S={staleness}: {fleet_score} vs sync {sync_score}"
    )
    if staleness == 0:
        assert fleet_score >= sync_score - 0.05, (
            f"S=0 must be score-equivalent: {fleet_score} vs {sync_score}"
        )


@pytest.fixture(scope="module")
def sync_score_baseline(tagger_config_text, data_dir):
    from spacy_ray_tpu.training.loop import train

    cfg = _config(
        tagger_config_text, data_dir,
        **{"training.max_steps": 40, "training.eval_frequency": 10},
    )
    _, res = train(cfg, n_workers=1, stdout_log=False)
    return res.best_score
