"""Collective performance observatory (collectives/observatory.py).

ISSUE 11 acceptance, pinned here:
  - timing-mode probe sampling (1-in-N cadence) feeds the labelled
    ``coll/hop_ms`` / ``coll/achieved_gbps`` metrics with the full label set
  - online-table round trip: a timed run persists a versioned table that a
    FRESH selector's measured mode consumes, and a decision FLIPS vs the
    model pick
  - alpha/beta refit converges on synthetic samples and lands in the
    selector (``calibrate``), changing model-mode estimates
  - drift detection fires on an injected slow hop: LOUD warning,
    ``coll:drift`` trace instant, profiler-capture arm
  - timing-mode-off (and -on!) hop programs are jaxpr-identical to today's:
    probes are separate dispatches, never ops in the traced program
  - table schema versioning: envelope + legacy list load, mismatch rejected
    with a warning, ``--merge`` fold semantics
"""

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu import telemetry
from deepspeed_tpu.collectives import observatory, selector
from deepspeed_tpu.collectives import table as table_mod
from deepspeed_tpu.utils.compat import shard_map

BLOCK = 64


@pytest.fixture
def mesh8():
    devs = jax.devices()[:8]
    return Mesh(np.array(devs), ("dp",))


@pytest.fixture(autouse=True)
def _reset():
    selector.configure()
    observatory.configure(enabled=False)
    yield
    selector.configure()
    observatory.configure(enabled=False)
    telemetry.configure(enabled=False)


@pytest.fixture
def dslog():
    """Route the repo logger into caplog (it defaults propagate=False)."""
    lg = logging.getLogger("deepspeed_tpu")
    prev = lg.propagate
    lg.propagate = True
    yield lg
    lg.propagate = prev


def _route_ring_int8(mesh):
    """Trace one ROUTED facade collective (registers a signature + census)."""

    def f(v):
        return dist.all_reduce(v, "dp", algorithm="ring", codec="int8",
                               block_size=BLOCK)

    x = jnp.ones((8, 4096), jnp.float32)
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                            check_vma=False))(x)
    out.block_until_ready()
    return out


# ------------------------------------------------------------ probe sampling


def test_probe_sampling_cadence_and_labels(mesh8):
    telemetry.configure(enabled=True)
    tracer = telemetry.get_tracer()
    tracer.reset()
    obs = observatory.configure(enabled=True, sample_every=2, persist=False,
                                probe_alternatives=False, refit_every=0,
                                async_compile=False)
    obs.install(mesh=mesh8)
    _route_ring_int8(mesh8)
    routes = obs.routes()
    assert len(routes) == 1
    r = routes[0]
    assert (r.op, r.algorithm, r.codec, r.backend) == (
        "all_reduce", "ring", "int8", "ppermute")
    # trace-time hop census: ring all-reduce on 8 ranks = 7 RS + 7 AG hops
    assert r.hops == 14
    assert r.wire_bytes > 0

    ran = [obs.on_step(s) for s in (1, 2, 3, 4)]
    # 1-in-2 cadence: steps 2 and 4 sample, 1 and 3 leave steady state alone
    assert ran == [0, 1, 0, 1]

    reg = tracer.registry
    from deepspeed_tpu.collectives.selector import _bytes_bucket

    labels = dict(op="all_reduce", algorithm="ring", codec="int8",
                  backend="ppermute", bucket=_bytes_bucket(r.nbytes), world=8)
    h = reg.peek_histogram("coll/hop_ms", **labels)
    assert h is not None and h.count == 2
    snap = reg.snapshot()
    gkeys = [k for k in snap if k.startswith("coll/achieved_gbps{")]
    assert gkeys and all(
        f'algorithm="ring"' in k and f'world="8"' in k for k in gkeys)
    assert snap["coll/probes"] == 2


def test_async_compile_warms_off_the_step_then_times(mesh8):
    """Production mode: a sampled step never pays a probe compile — the
    cold program is warmed on the background worker and a LATER sampled
    step times it."""
    import time

    obs = observatory.configure(enabled=True, sample_every=1, persist=False,
                                probe_alternatives=False, refit_every=0,
                                async_compile=True)
    obs.install(mesh=mesh8)
    _route_ring_int8(mesh8)
    assert obs.on_step(1) == 0  # cold: scheduled for background warm, not timed
    deadline = time.time() + 60
    while time.time() < deadline:
        entries = list(obs._probe_cache.values())
        if entries and entries[0][1] == "warm":
            break
        time.sleep(0.05)
    else:
        pytest.fail("background warm never completed")
    ran = 0
    for s in range(2, 6):  # the queue re-arms; the warm program gets timed
        ran += obs.on_step(s)
        if ran:
            break
    assert ran == 1
    assert obs.summary()["merged_samples"] == 1


def test_disabled_observatory_is_inert(mesh8):
    _route_ring_int8(mesh8)
    obs = observatory.get_observatory()
    assert obs.routes() == []
    assert obs.on_step(1) == 0


# -------------------------------------------------------- table round trip


def test_online_table_roundtrip_flips_decision(tmp_path):
    """A persisted online table changes a selector decision: the model pick
    for an exact-wire 1 MB all-reduce is the native lax baseline; observed
    rows showing ring beating it flip the fresh process's measured pick."""
    nbytes, world = 1 << 20, 8
    d0 = selector.select("all_reduce", nbytes, world)
    assert (d0.source, d0.algorithm) == ("model", "lax")

    obs = observatory.configure(enabled=True, persist=True,
                                table_path=str(tmp_path / "coll_table.json"),
                                refit_every=0)
    size_mb = nbytes / 1e6
    obs.record_sample(op="all_reduce", algorithm="ring", codec="none",
                      backend="ppermute", world=world, size_mb=size_mb,
                      latency_ms=0.2, itemsize=4)
    obs.record_sample(op="all_reduce", algorithm="lax", codec="none",
                      backend="xla", world=world, size_mb=size_mb,
                      latency_ms=5.0, itemsize=4)
    path = obs.persist()
    assert path and json.loads(open(path).read())["schema"] == table_mod.SCHEMA_VERSION

    # a FRESH selector (new process analog) warm-starts measured mode from
    # the persisted table — and the decision flips lax -> ring
    selector.configure(decision_table=path)
    d1 = selector.select("all_reduce", nbytes, world)
    assert (d1.source, d1.algorithm) == ("measured", "ring")


def test_real_probe_run_persists_consumable_table(mesh8, tmp_path):
    """End-to-end: real timed probes -> persisted envelope -> fresh
    measured-mode selector answers from it."""
    obs = observatory.configure(enabled=True, sample_every=1, persist=True,
                                table_path=str(tmp_path / "t.json"),
                                probe_alternatives=False, refit_every=0,
                                async_compile=False)
    obs.install(mesh=mesh8)
    _route_ring_int8(mesh8)
    assert obs.on_step(1) == 1
    rows = table_mod.load_table(str(tmp_path / "t.json"))
    assert rows and rows[0]["algorithm"] == "ring" and rows[0]["codec"] == "int8"
    assert rows[0]["backend"] == "ppermute" and rows[0]["latency_ms"] > 0
    selector.configure(decision_table=str(tmp_path / "t.json"), mode="measured",
                       codecs=("int8",), min_quant_bytes=0)
    d = selector.select("all_reduce", int(rows[0]["size_mb"] * 1e6), 8)
    assert d.source == "measured"


def test_ema_merge_damps_single_noisy_probe(tmp_path):
    obs = observatory.configure(enabled=True, persist=False, ema=0.25,
                                refit_every=0)
    kw = dict(op="all_reduce", algorithm="ring", codec="none",
              backend="ppermute", world=8, size_mb=1.0, itemsize=4)
    obs.record_sample(latency_ms=1.0, **kw)
    obs.record_sample(latency_ms=9.0, **kw)  # noisy outlier
    rows = obs.table_rows()
    assert len(rows) == 1
    # (1-0.25)*1.0 + 0.25*9.0 = 3.0 — one outlier cannot 9x the row
    assert rows[0]["latency_ms"] == pytest.approx(3.0, rel=1e-6)
    assert rows[0]["samples"] == 2


# ----------------------------------------------------------- schema version


def test_table_schema_envelope_and_legacy(tmp_path, caplog, dslog):
    rows = [{"op": "all_reduce", "world": 8, "size_mb": 1.0,
             "algorithm": "ring", "codec": "none", "backend": "ppermute",
             "latency_ms": 0.5}]
    p = tmp_path / "t.json"
    table_mod.write_table(str(p), rows, source="sweep")
    assert table_mod.load_table(str(p)) == [dict(rows[0])]
    # legacy bare-list files (PR-3 sweeps) still load
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps(rows))
    assert table_mod.load_table(str(legacy)) == rows
    # ... and so does the schema-LESS dict shape the selector used to accept
    legacy2 = tmp_path / "legacy2.json"
    legacy2.write_text(json.dumps({"rows": rows}))
    assert table_mod.load_table(str(legacy2)) == rows
    # a FUTURE schema is rejected with a warning, not mis-parsed
    future = tmp_path / "future.json"
    future.write_text(json.dumps({"schema": 99, "rows": rows}))
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        assert table_mod.load_table(str(future)) == []
    assert any("schema" in r.message for r in caplog.records)
    # and the selector treats that rejection as "no table" (model fallback)
    selector.configure(decision_table=str(future), mode="measured")
    assert selector.select("all_reduce", 1 << 20, 8).source == "model"


def test_merge_rows_semantics():
    base = [{"op": "all_reduce", "world": 8, "size_mb": 1.0,
             "algorithm": "ring", "codec": "none", "backend": "ppermute",
             "latency_ms": 4.0, "samples": 3},
            {"op": "all_gather", "world": 8, "size_mb": 2.0,
             "algorithm": "rhd", "codec": "none", "backend": "ppermute",
             "latency_ms": 1.0, "samples": 1}]
    fresh = [{"op": "all_reduce", "world": 8, "size_mb": 1.0,
              "algorithm": "ring", "codec": "none", "backend": "ppermute",
              "latency_ms": 2.0, "samples": 1}]
    # --merge (ema=None): the fresh sweep REPLACES the matching row's
    # numbers, uncovered rows survive
    out = {table_mod.row_key(r): r for r in table_mod.merge_rows(base, fresh)}
    assert out[table_mod.row_key(fresh[0])]["latency_ms"] == 2.0
    assert out[table_mod.row_key(fresh[0])]["samples"] == 4
    assert table_mod.row_key(base[1]) in out


def test_merge_replaces_legacy_unstamped_rows():
    """A legacy (pre-backend-stamp) row's merge identity defaults its
    backend from the algorithm name, so a fresh stamped measurement
    REPLACES it instead of leaving a stale duplicate that min-latency
    measured picks could route from forever."""
    legacy = [{"op": "all_reduce", "world": 8, "size_mb": 1.0,
               "algorithm": "ring", "codec": "int8", "latency_ms": 0.1}]
    fresh = [{"op": "all_reduce", "world": 8, "size_mb": 1.0,
              "algorithm": "ring", "codec": "int8", "backend": "ppermute",
              "latency_ms": 2.0, "samples": 1}]
    out = table_mod.merge_rows(legacy, fresh)
    assert len(out) == 1
    assert out[0]["latency_ms"] == 2.0
    # but DIFFERENT element widths at the same byte size are different
    # programs (a lossy wire costs per element) — they must not merge
    fp32 = [dict(fresh[0], itemsize=4)]
    assert len(table_mod.merge_rows(fresh, fp32)) == 2


def test_configure_drops_previous_engine_install(mesh8):
    """Reconfiguring (the next engine's hygiene) must drop the previous
    engine's mesh and profiler-arm callable — a drift event must never arm
    a torn-down engine's diagnostics."""
    obs = observatory.configure(enabled=True, persist=False)
    obs.install(mesh=mesh8, profiler_arm=lambda reason=None: None)
    assert obs._mesh is not None and obs.profiler_arm is not None
    obs = observatory.configure(enabled=False)
    assert obs._mesh is None and obs.profiler_arm is None


def test_sweep_cli_writes_envelope_and_merges(mesh8, tmp_path):
    from deepspeed_tpu.comm import benchmark

    out = tmp_path / "sweep.json"
    rc = benchmark.main(["--sweep", "--op", "all_reduce", "--sizes-mb", "0.01",
                         "--iters", "1", "--algorithms", "lax,ring",
                         "--output", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["schema"] == table_mod.SCHEMA_VERSION
    assert payload["source"] == "sweep"
    assert {r["algorithm"] for r in payload["rows"]} == {"lax", "ring"}
    assert all("itemsize" in r and "backend" in r for r in payload["rows"])
    # --merge folds a second sweep into the table, keeping uncovered rows
    extra = {"op": "all_gather", "world": 8, "size_mb": 9.0,
             "algorithm": "rhd", "codec": "none", "backend": "ppermute",
             "latency_ms": 1.0}
    table_mod.write_table(str(out), payload["rows"] + [extra], source="online")
    rc = benchmark.main(["--sweep", "--op", "all_reduce", "--sizes-mb", "0.01",
                         "--iters", "1", "--algorithms", "lax",
                         "--merge", str(out)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert merged["source"] == "merged"
    algs = {(r["op"], r["algorithm"]) for r in merged["rows"]}
    assert ("all_gather", "rhd") in algs and ("all_reduce", "ring") in algs


def _checkout_files(repo):
    """``{path: (mtime_ns, size)}`` of the checkout, less ``.git`` and what
    ``.gitignore`` names (caches and run outputs, which other xdist workers
    write meanwhile)."""
    import fnmatch
    import os

    with open(os.path.join(repo, ".gitignore"), encoding="utf-8") as f:
        ignored = [ln.strip().rstrip("/") for ln in f
                   if ln.strip() and ln[0] not in "#!"] + [".git"]

    def skip(rel):
        return any(fnmatch.fnmatch(rel, pat)
                   or fnmatch.fnmatch(os.path.basename(rel), pat)
                   for pat in ignored)

    seen = {}
    for dirpath, dirnames, filenames in os.walk(repo):
        rel_dir = os.path.relpath(dirpath, repo)
        dirnames[:] = [d for d in dirnames
                       if not skip(os.path.normpath(os.path.join(rel_dir, d)))]
        for name in filenames:
            rel = os.path.normpath(os.path.join(rel_dir, name))
            if not skip(rel):
                st = os.stat(os.path.join(dirpath, name))
                seen[rel] = (st.st_mtime_ns, st.st_size)
    return seen


def test_sweep_writes_its_output_and_nothing_else(mesh8, tmp_path):
    """A ``comm.benchmark --sweep`` run writes its ``--output`` file and
    creates or changes nothing else under the checkout — tier-1 used to
    dirty a tracked ledger file on every run."""
    import os

    from deepspeed_tpu.comm import benchmark

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    out = tmp_path / "sweep.json"
    before = _checkout_files(repo)
    rc = benchmark.main(["--sweep", "--op", "all_reduce", "--sizes-mb", "0.01",
                         "--iters", "1", "--algorithms", "lax,ring",
                         "--output", str(out)])
    assert rc == 0
    assert out.stat().st_size > 0
    assert sorted(os.listdir(tmp_path)) == ["sweep.json"]
    assert _checkout_files(repo) == before


def test_measured_pick_prefers_matching_itemsize(tmp_path):
    """A mixed-itemsize table answers each query from rows measured at the
    querying payload's element width: the bf16 rows (where int8 is only 2x
    wire compression) must not decide an fp32 payload's routing (4x)."""
    rows = [
        {"op": "all_reduce", "world": 8, "size_mb": 1.0, "algorithm": "ring",
         "codec": "int8", "backend": "ppermute", "latency_ms": 9.0,
         "itemsize": 2},
        {"op": "all_reduce", "world": 8, "size_mb": 1.0, "algorithm": "rhd",
         "codec": "int8", "backend": "ppermute", "latency_ms": 8.0,
         "itemsize": 2},
        {"op": "all_reduce", "world": 8, "size_mb": 1.0, "algorithm": "ring",
         "codec": "int8", "backend": "ppermute", "latency_ms": 1.0,
         "itemsize": 4},
    ]
    p = tmp_path / "mixed.json"
    table_mod.write_table(str(p), rows)
    selector.configure(decision_table=str(p), mode="measured",
                       codecs=("int8",), min_quant_bytes=0)
    d4 = selector.select("all_reduce", 1_000_000, 8, itemsize=4)
    assert (d4.algorithm, d4.est_us) == ("ring", 1000.0)
    d2 = selector.select("all_reduce", 1_000_000, 8, itemsize=2)
    assert d2.algorithm == "rhd"  # the bf16 rows' own winner


def test_merge_cli_never_clobbers_unreadable_base(mesh8, tmp_path, dslog,
                                                  caplog):
    """--sweep --merge onto a version-mismatched base leaves the base file
    untouched and lands the fresh sweep next to it."""
    from deepspeed_tpu.comm import benchmark

    base = tmp_path / "future.json"
    base.write_text(json.dumps({"schema": 99, "rows": [{"op": "all_reduce"}]}))
    before = base.read_text()
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        rc = benchmark.main(["--sweep", "--op", "all_reduce", "--sizes-mb",
                             "0.01", "--iters", "1", "--algorithms", "lax",
                             "--merge", str(base)])
    assert rc == 0
    assert base.read_text() == before  # the mismatched table survives
    side = tmp_path / "future.json.sweep.json"
    assert side.exists()
    assert json.loads(side.read_text())["rows"]


# ------------------------------------------------------------ alpha/beta fit


def test_alpha_beta_refit_converges_on_synthetic_samples():
    """Samples generated FROM the model at known constants refit back to
    them, and the calibration lands in the selector's estimates."""
    alpha, beta = 5.0, 20.0  # us/hop, us/MB
    obs = observatory.configure(enabled=True, persist=False, refit_every=0)
    for op, alg, size_mb in [("all_reduce", "ring", 0.5),
                             ("all_reduce", "rhd", 2.0),
                             ("all_gather", "ring", 1.0),
                             ("reduce_scatter", "bidir", 4.0),
                             ("all_reduce", "ring2d", 8.0)]:
        hops, wire_mb = observatory.model_terms(
            op, alg, "none", int(size_mb * 1e6), 8, 4)
        obs.record_sample(op=op, algorithm=alg, codec="none",
                          backend="ppermute", world=8, size_mb=size_mb,
                          latency_ms=(hops * alpha + wire_mb * beta) / 1e3,
                          itemsize=4)
    fitted = obs.refit()
    a, b = fitted["ppermute"]
    assert a == pytest.approx(alpha, rel=0.05)
    assert b == pytest.approx(beta, rel=0.05)
    # the selector now costs from the calibrated constants
    assert selector.get_config().backend_ab["ppermute"] == (a, b)
    est = selector.estimate_us("all_reduce", "ring", "none", 1 << 20, 8)
    hops, wire_mb = observatory.model_terms("all_reduce", "ring", "none",
                                            1 << 20, 8, 4)
    assert est == pytest.approx(hops * a + wire_mb * b, rel=1e-6)


def test_refit_decay_tracks_regime_change():
    """With forgetting on, a slowdown shows in the calibrated constants
    after a handful of refits instead of being averaged into history."""
    obs = observatory.configure(enabled=True, persist=False, refit_every=0,
                                fit_decay=0.5)

    def feed(alpha, n):
        for _ in range(n):
            hops, wire_mb = observatory.model_terms(
                "all_reduce", "ring", "none", 1 << 20, 8, 4)
            obs.record_sample(op="all_reduce", algorithm="ring", codec="none",
                              backend="ppermute", world=8, size_mb=1.0,
                              latency_ms=hops * alpha / 1e3, itemsize=4)

    feed(5.0, 8)
    obs.refit()
    assert obs.calibration["ppermute"][0] == pytest.approx(5.0, rel=0.05)
    for _ in range(6):  # regime change: 10x slower hops
        feed(50.0, 4)
        obs.refit()
    assert obs.calibration["ppermute"][0] == pytest.approx(50.0, rel=0.15)


def test_refit_fires_on_cadence(mesh8):
    obs = observatory.configure(enabled=True, sample_every=1, persist=False,
                                refit_every=2, probe_alternatives=False,
                                async_compile=False)
    obs.install(mesh=mesh8)
    _route_ring_int8(mesh8)
    for s in range(1, 5):
        obs.on_step(s)
    assert "ppermute" in obs.calibration
    assert selector.get_config().backend_ab.get("ppermute") is not None


# ------------------------------------------------------------------- drift


def test_drift_warns_arms_profiler_and_traces(mesh8, tmp_path, caplog, dslog):
    telemetry.configure(enabled=True)
    telemetry.get_tracer().reset()
    obs = observatory.configure(enabled=True, sample_every=1, persist=False,
                                refit_every=2, drift_ratio=3.0,
                                probe_alternatives=False, async_compile=False)
    obs.install(mesh=mesh8)
    _route_ring_int8(mesh8)
    # both phases read an INJECTED clock: calibrating against real CPU
    # timings made the baseline depend on what the other xdist workers were
    # doing (a loaded box calibrates slow, and 5 s was then < 3x of it)
    obs._timer = lambda f, x, iters, warmup: 1e-3  # healthy hop: 1 ms
    for s in range(1, 4):  # calibrate first (drift needs a trusted model)
        obs.on_step(s)
    assert "ppermute" in obs.calibration

    armed = []
    obs.profiler_arm = lambda reason=None: armed.append(reason)
    obs._timer = lambda f, x, iters, warmup: 5.0  # injected slow hop: 5 s
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        for s in range(4, 10):
            obs.on_step(s)
            if obs.drift_events:
                break
    assert obs.drift_events >= 1
    assert any("COLLECTIVE DRIFT" in r.message for r in caplog.records)
    assert armed and armed[0].startswith("coll_drift:")
    instants = [e for e in telemetry.get_tracer().events()
                if e.get("name") == "coll:drift"]
    assert instants and instants[0]["args"]["ratio"] > 3.0
    reg = telemetry.get_tracer().registry
    ratios = [k for k in reg.gauges() if k.startswith("coll/model_ratio{")]
    assert ratios


def test_no_drift_alarm_against_uncalibrated_model(mesh8, caplog, dslog):
    """The hand-set alpha/beta constants are NOT a drift baseline: before
    any calibration/measured rows exist, probes observe without alarming
    (a never-tuned mesh would otherwise cry wolf on its first sample)."""
    obs = observatory.configure(enabled=True, sample_every=1, persist=False,
                                refit_every=0, probe_alternatives=False,
                                async_compile=False)
    obs.install(mesh=mesh8)
    _route_ring_int8(mesh8)
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        obs.on_step(1)
    assert obs.drift_events == 0
    assert not any("COLLECTIVE DRIFT" in r.message for r in caplog.records)


# ------------------------------------------------------- program identity


def test_observatory_never_touches_the_traced_program(mesh8):
    """THE structural acceptance: hop programs are jaxpr-identical with the
    observatory off, on, and absent — its timings come from standalone
    probe dispatches, never from ops added to the step."""

    def make():
        # a FRESH closure per trace: shard_map caches the traced body per
        # function identity, and a cache hit would skip the second trace
        def f(v):
            return dist.all_reduce(v, "dp", algorithm="ring", codec="int8",
                                   block_size=BLOCK)

        return shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"),
                         check_vma=False)

    x = jnp.ones((8, 4096), jnp.float32)
    observatory.configure(enabled=False)
    j_off = str(jax.make_jaxpr(make())(x))
    obs = observatory.configure(enabled=True, sample_every=1, persist=False)
    obs.install(mesh=mesh8)
    j_on = str(jax.make_jaxpr(make())(x))
    assert j_on == j_off
    # and the census DID observe the enabled trace
    assert obs.routes() and obs.routes()[0].hops == 14


def test_hlo_wire_reconciliation_in_program_registry(mesh8):
    """A captured routed program reconciles the observatory's traced wire
    bytes against its HLO-extracted collective bytes (the ppermute hops ARE
    the collectives in this program, so the ratio sits near 1)."""
    from deepspeed_tpu.telemetry.programs import get_program_registry

    telemetry.configure(enabled=True)
    reg = get_program_registry()
    reg.reset()
    obs = observatory.configure(enabled=True, persist=False)
    obs.install(mesh=mesh8)

    def f(v):
        return dist.all_reduce(v, "dp", algorithm="ring", codec="int8",
                               block_size=BLOCK)

    fn = jax.jit(shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"),
                           check_vma=False))
    wrapped = reg.wrap(fn, "coll_probe_program")
    wrapped(jnp.ones((8, 4096), jnp.float32)).block_until_ready()
    rec = reg.latest("coll_probe_program")
    assert rec is not None
    assert rec.routed_wire_bytes > 0
    assert rec.routed_wire_bytes == obs.routes()[0].wire_bytes
    assert rec.wire_ratio is not None and 0.5 < rec.wire_ratio < 2.0
    key = 'coll/wire_bytes_ratio{program="coll_probe_program"}'
    assert key in telemetry.get_tracer().registry.gauges()


# ------------------------------------------------------------ engine wiring


def test_engine_installs_observatory_and_steps():
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    tc = TransformerConfig(vocab_size=32, hidden_size=16, intermediate_size=32,
                           num_layers=1, num_heads=2, max_seq_len=16)
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(tc, example_seq_len=8),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 10_000,
                "collectives": {"enabled": True,
                                "observe": {"enabled": True,
                                            "sample_every": 1,
                                            "persist": False}}})
    assert engine._coll_observatory is not None
    assert observatory.get_observatory().enabled
    batch = {"input_ids": np.zeros((engine.train_batch_size, 8), np.int32)}
    engine.train_batch(batch)  # on_step runs (no routed signatures: no-op)
    # an engine WITHOUT the observatory resets the process-global instance
    deepspeed_tpu.initialize(
        model=causal_lm_spec(tc, example_seq_len=8),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 10_000})
    assert not observatory.get_observatory().enabled
