"""Observability subsystem (DESIGN.md §14): recorder, traffic ledger,
modeled-vs-measured reconciliation, report rendering.

The load-bearing contract: the ledger counts the bits that ACTUALLY
cross each protocol boundary (jax.debug.callback taps next to the real
transport ops), and every round those counts must equal
``sysmodel.traffic.round_traffic_breakdown`` exactly — for every scheme,
codec and cohort size, including migration payloads. A deliberately
corrupted price must trip the diff (the check can actually fail).
The disabled recorder must leave the jitted round graph untouched
(bit-identical losses) and cost ≲2% wall-clock.
"""
import json
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs.paper_cnn import LIGHT_CONFIG  # noqa: E402
from repro.core.simulator import FedSimulator, SimConfig  # noqa: E402
from repro.obs import report as report_mod  # noqa: E402
from repro.obs.ledger import (LEDGER_CATEGORIES, TrafficLedger,  # noqa: E402
                              reconcile, reconcile_events, totals)
from repro.obs.recorder import (Recorder, read_events,  # noqa: E402
                                read_manifest)

N, BATCH = 4, 8


def _data(k, tau=1, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(k, tau, BATCH, 28, 28, 1).astype(np.float32),
            rng.randint(0, 10, (k, tau, BATCH)))


def _sim(scheme="sfl_ga", cut=2, n=N, **kw):
    return FedSimulator(
        LIGHT_CONFIG,
        SimConfig(scheme=scheme, cut=cut, n_clients=n, batch=BATCH, **kw),
        seed=0)


def _instrumented_run(scheme, rounds=2, tau=2, migrate_to=None, **kw):
    """Run ``rounds`` instrumented rounds (+ optional migration) and
    return the recorder. The sim MUST be built under the recorder —
    instrumented objects capture it at construction."""
    rec = Recorder()  # in-memory
    with obs.use_recorder(rec):
        sim = _sim(scheme=scheme, tau=tau, **kw)
        k = sim.n_participants
        for r in range(rounds):
            sim.run_round(*_data(k, tau=tau, seed=r))
        if migrate_to is not None:
            sim.set_cut(migrate_to)
            sim.run_round(*_data(k, tau=tau, seed=rounds))
    return rec


# ------------------------------------------------------------ reconciliation
class TestReconciliation:
    @pytest.mark.parametrize("codec", ["fp32", "int8"])
    @pytest.mark.parametrize("scheme", ["sfl_ga", "psl", "sfl", "fl"])
    def test_exact_all_schemes_and_codecs(self, scheme, codec):
        migrate = 3 if scheme != "fl" else None
        rec = _instrumented_run(scheme, migrate_to=migrate,
                                uplink_codec=codec, downlink_codec=codec)
        rows, bad = reconcile_events(rec.events)
        n_rounds = 2 if scheme == "fl" else 3
        n_migr = 0 if scheme == "fl" else 1
        assert len(rows) == n_rounds + n_migr
        assert bad == 0, [r["mismatches"] for r in rows if r["mismatches"]]
        # measured traffic is genuinely non-trivial, not vacuous zeros
        for row in rows:
            assert row["measured"]["total_bits"] > 0
            assert row["measured"] == row["modeled"]

    def test_exact_under_partial_participation(self):
        rec = _instrumented_run("sfl_ga", cohort=3, sampler="uniform",
                                migrate_to=3, uplink_codec="int8")
        rows, bad = reconcile_events(rec.events)
        assert bad == 0
        # priced for the K participants, not the whole bank
        tr = [e for e in rec.events if e["kind"] == "traffic"]
        assert all(e["participants"] == 3 for e in tr)

    def test_corrupted_price_trips_the_diff(self, monkeypatch):
        """A deliberately wrong model price MUST show up as a mismatch —
        proves the reconciliation can actually fail (it is a check, not
        a tautology that copies one side into the other)."""
        import repro.sysmodel.traffic as traffic

        true_breakdown = traffic.round_traffic_breakdown

        def corrupted(*a, **kw):
            out = dict(true_breakdown(*a, **kw))
            out["up_smashed"] += 64  # pricing bug: 64 phantom bits
            return out

        monkeypatch.setattr(traffic, "round_traffic_breakdown", corrupted)
        rec = _instrumented_run("sfl_ga", rounds=1)
        rows, bad = reconcile_events(rec.events)
        assert bad == 1
        (mism,) = rows[0]["mismatches"]
        assert mism["category"] == "up_smashed"
        assert mism["delta_bits"] == -64  # measured has 64 fewer than modeled

    def test_migration_measured_equals_modeled(self):
        """set_cut in BOTH directions: bits from the tensors that really
        changed sides == sysmodel.traffic.migration_bits."""
        rec = Recorder()
        with obs.use_recorder(rec):
            sim = _sim(tau=1)
            sim.run_round(*_data(N))
            sim.set_cut(3)   # server->client: downlink broadcast
            sim.set_cut(1)   # client->server: uplink merge
        migr = [e for e in rec.events if e["kind"] == "migration"]
        assert len(migr) == 2
        down, up = migr
        assert down["measured"] == down["modeled"]
        assert up["measured"] == up["modeled"]
        assert down["measured"]["down_bits"] > 0 == down["measured"]["up_bits"]
        assert up["measured"]["up_bits"] > 0 == up["measured"]["down_bits"]

    @pytest.mark.parametrize("scheme", ["sfl_ga", "psl", "sfl", "fl"])
    def test_breakdown_sums_to_round_traffic_bits(self, scheme):
        from repro.sysmodel.traffic import (round_traffic_bits,
                                            round_traffic_breakdown)

        kw = dict(n_clients=5, tau=3, smashed_elems=1234, label_bits=256,
                  client_model_bits=777, full_model_bits=9999,
                  uplink_codec="int8", downlink_codec="int4")
        br = round_traffic_breakdown(scheme, **kw)
        assert set(br) == set(LEDGER_CATEGORIES)
        assert totals(br) == round_traffic_bits(scheme, **kw)

    def test_ledger_primitives(self):
        led = TrafficLedger()
        led.add("up_smashed", 100)
        led.add("up_smashed", 20)
        led.add("down_grad", 7)
        with pytest.raises(KeyError):
            led.add("sideways", 1)
        snap = led.snapshot_and_reset()
        assert snap["up_smashed"] == 120 and snap["down_grad"] == 7
        assert all(v == 0 for v in led.peek().values())
        assert reconcile(snap, snap) == []
        rows = reconcile(snap, {**snap, "down_grad": 8})
        assert rows == [{"category": "down_grad", "measured_bits": 7,
                         "modeled_bits": 8, "delta_bits": -1}]


# ------------------------------------------------------------ recorder core
class TestRecorder:
    def test_span_nesting_and_order(self):
        rec = Recorder()
        with rec.span("outer", cut=2):
            with rec.span("inner"):
                pass
            with rec.span("inner2"):
                pass
        spans = {e["name"]: e for e in rec.events if e["kind"] == "span"}
        assert spans["inner"]["depth"] == 1
        assert spans["inner"]["parent"] == "outer"
        assert spans["outer"]["depth"] == 0 and spans["outer"]["parent"] is None
        # closing-time emission: children precede the parent in the stream
        names = [e["name"] for e in rec.events if e["kind"] == "span"]
        assert names == ["inner", "inner2", "outer"]
        assert spans["outer"]["dur_s"] >= spans["inner"]["dur_s"]
        assert spans["outer"]["cut"] == 2

    def test_round_scope_and_seq(self):
        rec = Recorder()
        rec.gauge("pre", 1.0)
        rec.set_round(0)
        rec.counter("steps")
        rec.set_round(1)
        rec.counter("steps")
        rec.set_round(None)
        rec.gauge("post", 2.0)
        rounds = [e["round"] for e in rec.events]
        assert rounds == [None, 0, 1, None]
        seqs = [e["seq"] for e in rec.events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)

    def test_jsonl_roundtrip_and_sanitization(self, tmp_path):
        d = str(tmp_path / "m")
        rec = Recorder(d, config={"lr": 0.1, "bad": float("nan")},
                       flush_every=2)
        rec.gauge("latency", float("inf"))
        rec.event("traffic", name="t", measured={"x": 1},
                  nested={"v": float("nan")})
        rec.counter("rounds", 2)
        rec.close()
        evs = read_events(d)
        # every line parsed back; summary appended on close
        assert [e["kind"] for e in evs] == ["gauge", "traffic", "counter",
                                           "summary"]
        assert evs[0]["value"] == "inf"          # sanitized, not corrupt JSON
        assert evs[1]["nested"]["v"] == "nan"
        assert evs[3]["counters"] == {"rounds": 2}
        man = read_manifest(d)
        assert man["schema"] == "repro.obs.v1"
        assert man["config"]["lr"] == 0.1
        assert len(man["config_hash"]) == 12
        # corrupt/blank lines are skipped, not fatal
        with open(os.path.join(d, "events.jsonl"), "a") as f:
            f.write("\n{not json}\n")
        assert len(read_events(d)) == len(evs)

    def test_emit_from_jit_fires_per_execution(self):
        import jax.numpy as jnp

        rec = Recorder()

        @jax.jit
        def f(x):
            rec.emit_from_jit("x2", x * 2)
            return x + 1

        f(jnp.float32(3.0))
        f(jnp.float32(4.0))   # cached executable still fires the callback
        jax.effects_barrier()
        vals = [e["value"] for e in rec.events if e["name"] == "x2"]
        assert sorted(vals) == [6.0, 8.0]

    def test_null_recorder_is_inert(self, capsys):
        nr = obs.null_recorder
        assert not nr.enabled and nr.ledger is None
        with nr.span("x"):
            nr.counter("c")
            nr.gauge("g", 1.0)
            nr.event("traffic", name="t")
        obs.set_quiet(True)
        try:
            obs.log("should not appear")
            assert capsys.readouterr().err == ""
        finally:
            obs.set_quiet(False)


# ---------------------------------------------------- non-perturbation/cost
class TestDisabledPath:
    def test_enabled_recorder_does_not_perturb_training(self):
        """Taps are side-effect-only: losses with metrics ON must equal
        the metrics-OFF run bit for bit (same graph, same seeds)."""
        def losses(rec):
            with obs.use_recorder(rec):
                sim = _sim(tau=2, uplink_codec="int8")
                return [sim.run_round(*_data(N, tau=2, seed=r))["loss"]
                        for r in range(3)]

        off = losses(None)  # use_recorder(None) installs the Null default
        on = losses(Recorder())
        assert off == on

    def test_disabled_overhead_within_2pct(self):
        """The disabled path costs ONE attribute check per round on top
        of the pre-obs code. Bound it directly: 20 rounds' worth of
        guard work must be <2% of a measured 20-round run."""
        sim = _sim(tau=1)
        x, y = _data(N)
        sim.run_round(x, y)  # warm the jit cache
        t0 = time.perf_counter()
        for _ in range(20):
            sim.run_round(x, y)
        t_run = time.perf_counter() - t0

        rec = sim._rec  # the NullRecorder captured at construction
        reps = 20_000
        t0 = time.perf_counter()
        for _ in range(reps):
            if rec.enabled:  # pragma: no cover - the guard under test
                raise AssertionError
        t_guard = (time.perf_counter() - t0) / reps * 20
        assert t_guard < 0.02 * t_run, (t_guard, t_run)


# ----------------------------------------------------------------- resume
class TestResume:
    def test_append_continues_round_indices(self, tmp_path):
        d = str(tmp_path / "metrics")
        ck = str(tmp_path / "sim.ckpt")
        kw = dict(tau=1, cohort=3, sampler="uniform")

        rec1 = Recorder(d, config={"phase": 1})
        with obs.use_recorder(rec1):
            sim = _sim(**kw)
            for r in range(3):
                sim.run_round(*_data(3, seed=r))
            sim.save(ck)
        rec1.close()
        man1 = read_manifest(d)

        rec2 = Recorder(d, config={"phase": 2}, append=True)
        with obs.use_recorder(rec2):
            sim2 = _sim(**kw)
            sim2.restore(ck)
            for r in range(3, 5):
                sim2.run_round(*_data(3, seed=r))
        rec2.close()

        evs = read_events(d)
        rounds = [e["round"] for e in evs if e["kind"] == "round"]
        assert rounds == [0, 1, 2, 3, 4]  # continued, no duplicates
        traffic = [e["round"] for e in evs if e["kind"] == "traffic"]
        assert traffic == [0, 1, 2, 3, 4]
        _, bad = reconcile_events(evs)
        assert bad == 0
        # append keeps the original manifest (one provenance per run dir)
        assert read_manifest(d) == man1


# ----------------------------------------------------------------- report
class TestReport:
    def _run_dir(self, tmp_path):
        d = str(tmp_path / "run")
        rec = Recorder(d, config={"arch": "paper-cnn"})
        with obs.use_recorder(rec):
            sim = _sim(tau=2, uplink_codec="int8")
            for r in range(2):
                sim.run_round(*_data(N, tau=2, seed=r))
            sim.set_cut(3)
            sim.run_round(*_data(N, tau=2, seed=2))
        rec.close()
        return d

    def test_report_renders_and_exits_clean(self, tmp_path, capsys):
        d = self._run_dir(tmp_path)
        code = report_mod.main([d])
        out = capsys.readouterr().out
        assert code == 0
        assert "manifest" in out and "timeline" in out
        assert "reconcile exactly" in out

    def test_report_exits_nonzero_on_mismatch(self, tmp_path, capsys):
        d = self._run_dir(tmp_path)
        # corrupt one traffic event's model price on disk
        path = os.path.join(d, "events.jsonl")
        lines = open(path).read().splitlines()
        for i, ln in enumerate(lines):
            ev = json.loads(ln)
            if ev["kind"] == "traffic":
                ev["modeled"]["up_smashed"] += 8
                lines[i] = json.dumps(ev)
                break
        open(path, "w").write("\n".join(lines) + "\n")
        assert report_mod.main([d]) == 1
        assert "!!" in capsys.readouterr().out

    def test_report_missing_dir(self, capsys):
        assert report_mod.main(["/nonexistent/run"]) == 2


# ------------------------------------------------- spans on the profiler
def _host_events(tmp_path, fn):
    """Run ``fn`` under a CPU profiler trace; the ``/host:CPU`` events as
    (line, name, start_ns, end_ns)."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(line.name, e.name, e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
    return out


def _only(events, name):
    (ev,) = [e for e in events if e[1] == name]
    return ev


def _lm_setup(n=3):
    from repro.configs import TrainConfig, get_config, reduced_config
    from repro.core import algorithms as alg
    from repro.models import lm
    from repro.optim import make_optimizer

    cfg = reduced_config(get_config("granite-8b")).with_overrides(
        num_layers=2, d_model=64, d_ff=128, vocab_size=256,
        num_heads=2, num_kv_heads=1, head_dim=32)
    plan = lm.build_plan(cfg, 1)
    params = alg.split_lm_params(
        lm.init_lm(jax.random.key(0), plan, jnp.float32), n)
    tcfg = TrainConfig(model=cfg, algo="sfl_ga", cut_layer=1,
                       compute_dtype="float32", remat=False)
    opt = make_optimizer("adamw", 1e-3)
    return alg, plan, tcfg, opt, params


class TestProfilerSpans:
    @pytest.mark.parametrize("real", [False, True], ids=["null", "recorder"])
    def test_spans_reach_the_trace_nested(self, tmp_path, real):
        rec = Recorder() if real else obs.null_recorder

        def body():
            with rec.span("obs-test/outer", cut=2):
                with rec.span("obs-test/inner"):
                    time.sleep(0.002)

        evs = _host_events(tmp_path, body)
        outer = _only(evs, "obs-test/outer")
        inner = _only(evs, "obs-test/inner")
        assert outer[0] == inner[0]  # one thread's line
        assert outer[2] <= inner[2] and inner[3] <= outer[3]
        assert inner[3] - inner[2] >= 2_000_000

    def test_recorder_span_records_are_unchanged(self):
        import threading

        rec = Recorder()
        with rec.span("outer", cut=2):
            worker = threading.Thread(
                target=lambda: rec.span("worker").__enter__().__exit__(
                    None, None, None))
            worker.start()
            worker.join(timeout=10)
            assert not worker.is_alive()
            with rec.span("inner"):
                pass
        spans = [e for e in rec.events if e["kind"] == "span"]
        assert [e["name"] for e in spans] == ["worker", "inner", "outer"]
        base = {"kind", "name", "dur_s", "depth", "parent", "round", "seq",
                "ts"}
        assert set(spans[1]) == base and set(spans[2]) == base | {"cut"}
        # a worker thread's span nests under nothing of the main thread's
        assert (spans[0]["depth"], spans[0]["parent"]) == (0, None)
        assert (spans[1]["depth"], spans[1]["parent"]) == (1, "outer")

    def test_cohort_gather_and_scatter_spans(self, tmp_path):
        alg, _, _, opt, params = _lm_setup()
        opt_state = opt.init(params)
        idx = np.asarray([0, 2])

        def body():
            cp = alg.gather_cohort(params, idx)
            alg.scatter_cohort(params, cp, idx)
            cop = alg.gather_cohort_opt(opt_state, idx)
            alg.scatter_cohort_opt(opt_state, cop, idx)

        names = [e[1] for e in _host_events(tmp_path, body)]
        # the _opt forms nest one span per moment inside their own
        assert names.count("bank/gather") == 1 + 1 + 2
        assert names.count("bank/scatter") == 1 + 1 + 2
        rec = Recorder()
        with obs.use_recorder(rec):
            body()
        spans = [e for e in rec.events if e["kind"] == "span"]
        assert {e["name"] for e in spans} == {"bank/gather", "bank/scatter"}
        assert {e["parent"] for e in spans if e["depth"] == 1} == {
            "bank/gather", "bank/scatter"}

    def test_client_bank_spans_replace_its_gauges(self):
        from repro.core.bank import ClientBank

        rec = Recorder()
        tree = {"w": np.arange(24, dtype=np.float32).reshape(6, 4)}
        want = tree["w"][[1, 3]] + 1.0  # the host bank keeps ``tree``'s rows
        with obs.use_recorder(rec):
            bank = ClientBank(tree, n_clients=6, stacked=True,
                              backend="host")
        with bank:
            bank.prefetch(0, [1, 3])
            cohort = bank.gather([1, 3], t=0)
            bank.scatter([1, 3], {"w": cohort["w"] + 1.0})
            bank.flush()
        names = {e["name"] for e in rec.events if e["kind"] == "span"}
        assert names == {"bank/prefetch", "bank/wait", "bank/gather",
                         "bank/drain", "bank/scatter"}
        assert not [e for e in rec.events if e["kind"] == "gauge"]
        assert bank.stats()["prefetch_hits"] == 1
        np.testing.assert_array_equal(bank.tree["w"][[1, 3]], want)

    def test_train_step_is_untouched_by_tracing(self, tmp_path):
        """gather → step → scatter inside a profiler trace gives the same
        bits as outside one, and the step's program holds no host
        callback: the spans stage nothing into jitted code."""
        alg, plan, tcfg, opt, params = _lm_setup()
        step = jax.jit(alg.make_train_step(plan, tcfg, opt, 2))
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, 256, (2, 2, 16))),
                 "labels": jnp.asarray(rng.randint(0, 256, (2, 2, 16))),
                 "rho": jnp.asarray([0.5, 0.5])}
        idx = np.asarray([0, 2])

        def one():
            opt_state = opt.init(params)
            cp, cop, m = step(alg.gather_cohort(params, idx),
                              alg.gather_cohort_opt(opt_state, idx), batch)
            new = alg.scatter_cohort(params, cp, idx)
            return jax.tree.map(np.asarray, (new, m["loss"]))

        outside = one()
        inside = []
        _host_events(tmp_path, lambda: inside.append(one()))
        for a, b in zip(jax.tree.leaves(outside), jax.tree.leaves(inside[0])):
            np.testing.assert_array_equal(a, b)
        text = step.lower(alg.gather_cohort(params, idx),
                          alg.gather_cohort_opt(opt.init(params), idx),
                          batch).as_text()
        assert "callback" not in text

    def test_serve_token_stats_without_a_device_read(self, monkeypatch):
        """The serve_token occupancy comes from the host's lengths: equal,
        step by step and through retirements, to what the caches' own
        lengths give; with no recorder the stats are not built at all."""
        from repro.configs import get_config, reduced_config
        from repro.core.serve_engine import ServeEngine, make_requests
        from repro.models import lm, paging

        cfg = reduced_config(get_config("granite-8b"))
        plan = lm.build_plan(cfg, 1)
        params = lm.init_lm(jax.random.key(0), plan, jnp.float32)

        def device_stats(caches):  # the device read the engine no longer makes
            toks = pages = 0
            for gc in caches:
                for c in gc:
                    if isinstance(c, paging.PagedKVCache):
                        L, page = c.page_table.shape[0], c.pages_k.shape[3]
                        lens = np.asarray(c.lengths[0])
                        toks += int(lens.sum()) * L
                        pages += sum(paging.pages_for(int(t), page)
                                     for t in lens) * L
            return toks, pages

        rec = Recorder()
        with obs.use_recorder(rec):
            eng = ServeEngine(params, plan, slots=3, max_len=24,
                              page_size=8, seed=0)
        for r in make_requests(5, 7, [4, 2, 10], vocab_size=cfg.vocab_size):
            eng.submit(r)
        seen = retired = 0
        while eng.queue or eng._live.any():
            eng.step()
            evs = [e for e in rec.events if e["kind"] == "serve_token"]
            if len(evs) == seen:
                continue
            seen = len(evs)
            retired += evs[-1]["retired"]
            assert (evs[-1]["live_tokens"], evs[-1]["pages_in_use"]) == \
                device_stats(eng.caches)
        assert retired == 5 and seen > 3
        spans = {e["name"] for e in rec.events if e["kind"] == "span"}
        assert spans == {"serve/admit", "serve/prefill", "serve/adopt",
                         "serve/tables", "serve/decode", "serve/retire"}
        null_eng = ServeEngine(params, plan, slots=3, max_len=24,
                               page_size=8, seed=0)
        assert not null_eng._rec.enabled

        def refuse(*a):
            raise AssertionError("occupancy built with no recorder")

        monkeypatch.setattr(paging, "paged_cache_stats", refuse)
        null_eng.submit(make_requests(1, 7, 2, vocab_size=cfg.vocab_size)[0])
        null_eng.run()
        assert len(null_eng.completions) == 1
