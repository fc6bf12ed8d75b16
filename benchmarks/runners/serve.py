"""Serving cells: ``InferenceEngineV2.generate`` under open-loop arrivals
(``traffic.kind: open_loop``) or closed waves (``closed_waves``).

The workload file gives the engine's own config (``engine``) and the traffic.
Set-up: bf16 weights made on the device from ``--seed`` in one jitted call,
the engine with its KV pool, the check against the plain reference, then one
run of every program the cell's traffic can reach (``warm``). Per-request
times are the engine's own ``RequestRecord`` stamps (``perf_counter``:
arrival = the time the request was DUE, first token, finish), which need
``flight_recorder`` on in the engine config. With a trace directory, the
calls into the engine's two dispatch methods are wrapped to record rows and
context per call, and a few seconds in the middle of the window run under
the profiler.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

from benchmarks.lib import harness, program, stats, traffic

CHECK_PROMPTS = 4
CHECK_DECODE_STEPS = 2
CHECK_GENERATED = 10
TRACE_START_SHARE = 0.3  # of --seconds
TRACE_SECONDS = 3.0
# How far below the reference's best logit the reference's logit of a token
# that ``generate`` picked may lie, in units of that row's RMS in the
# reference's own fp32 logits, as a multiple of the configuration's own
# ``check.logit_rel_tol`` (relative L2 error of last-position logits against
# the fp32 reference on the same weights; each configuration file states it
# with the readings behind it, and there is no default). Logits within that
# tolerance of the reference's are off by tolerance x RMS each on average and
# by 4.5 times that at the worst of some 50,000 (Gaussian errors), and a wrong
# pick needs two of them.
TOKEN_GAP_PER_LOGIT_TOL = 2 * 4.5


def make_weights(model_cfg, seed):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import CausalLM

    @jax.jit
    def make(key):
        params = CausalLM(model_cfg).init(
            {"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)

    return make(jax.random.PRNGKey(seed & 0x7FFFFFFF))


class Pinned:
    """The reference at the program's own expert picks, and the audit of
    those picks, for an architecture that says it is routed. A rounding that
    changes which experts a token visits moves the plain comparison by many
    times bf16's own error (PERF.md, section 2), so the reference is sent
    where the program went, and the picks are held to the reference's own
    scores: a router that could not have made them fails by its shortfall."""

    def __init__(self, reference, routing, cfg, weights, shape):
        import jax

        self.routing, self.weights = routing, weights
        self.run = jax.jit(lambda w, t, p: (reference.forward(w, cfg, t, p),
                                            reference.route_shortfall(w, cfg, t, p)))
        # a position that was not fed keeps experts 0..k-1: the model is causal,
        # so it cannot reach a compared row, and it is left out of the audit
        self.blank = np.broadcast_to(np.arange(routing.k, dtype=np.int32),
                                     tuple(shape) + (routing.layers, routing.k))
        self.shortfall, self.flips, self.audited = -np.inf, 0, 0
        self.clear()

    def clear(self):
        self.picks = self.blank.copy()
        self.fed = np.zeros(self.blank.shape[:2], bool)

    def record(self, starts, lengths, picks):
        """``picks[i]``: the experts of the ``lengths[i]`` tokens fed to row i from ``starts[i]`` on."""
        if len(picks) != len(starts):
            raise ValueError(f"picks for {len(picks)} rows, {len(starts)} were fed")
        for i, (start, n) in enumerate(zip(starts, lengths)):
            self.picks[i, start:start + n] = program.checked_picks(picks[i], n, self.routing)
            self.fed[i, start:start + n] = True

    def logits(self, tokens):
        """The pinned reference's logits, the audit taken along the same pass."""
        import jax.numpy as jnp

        want, shortfall = self.run(self.weights, jnp.asarray(tokens), jnp.asarray(self.picks))
        shortfall = np.asarray(shortfall)[self.fed]  # [positions fed, routed layers]
        self.shortfall = max(self.shortfall, float(shortfall.max()))
        self.flips += int((shortfall > 0).sum())
        self.audited += shortfall.size
        return np.asarray(want)


def check(engine, reference, architecture, config, seed):
    """Prefill logits and logits of further tokens fed through the cache (the
    ``put`` path), then the tokens ``generate`` picks (the fused prefill and
    decode-chain programs), each against the reference's full forward; where
    the architecture is routed, against the reference at the program's own
    expert picks, and the picks against the reference's own scores. Returns
    ``correct`` and every number compared beside its limit."""
    import jax
    import jax.numpy as jnp

    logit_tol = program.tolerance(config, "logit_rel_tol")
    limits = {"logit_rel_err": logit_tol, "token_gap": TOKEN_GAP_PER_LOGIT_TOL * logit_tol}
    routing = program.routing(architecture, config)
    cfg = program.published(config)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 7])
    bucket = engine.config.chunk_bucket
    # the lengths come from the seed, the shapes do not: a shape of its own for
    # every seed would compile the reference anew in every run
    lens = rng.integers(bucket // 2, bucket - CHECK_DECODE_STEPS, CHECK_PROMPTS)
    total = bucket + CHECK_GENERATED
    seqs = rng.integers(0, config["vocab_size"], (CHECK_PROMPTS, total), dtype=np.int32)
    weights = architecture.reference_weights(engine.params)
    ref_forward = jax.jit(lambda w, t: reference.forward(w, cfg, t))

    def plain(tokens):
        return np.asarray(ref_forward(weights, jnp.asarray(tokens)))

    pinned = None
    if routing:
        limits["route_shortfall"] = program.tolerance(config, "route_shortfall_tol")
        pinned = Pinned(reference, routing, cfg, weights, seqs.shape)
    wanted = pinned.logits if pinned else plain

    uids = list(range(10_000, 10_000 + CHECK_PROMPTS))
    got = []
    for step in range(CHECK_DECODE_STEPS + 1):
        starts = [0 if step == 0 else lens[i] + step - 1 for i in range(CHECK_PROMPTS)]
        fed = [seqs[i, starts[i]:lens[i] + step] for i in range(CHECK_PROMPTS)]
        if pinned:
            logits, picks = routing.put(engine, uids, fed)
            pinned.record(starts, [len(f) for f in fed], picks)
        else:
            logits = engine.put(uids, fed)
        got.append(np.asarray(logits, np.float32))
    for uid in uids:
        engine.flush(uid)
    # one pass for the three steps: a row at position p sees positions up to p alone
    want = wanted(seqs)
    errs = [program.relative_error(got[step], np.stack([want[i, lens[i] + step - 1]
                                                        for i in range(CHECK_PROMPTS)]))
            for step in range(CHECK_DECODE_STEPS + 1)]

    prompts = [seqs[i, :lens[i]] for i in range(CHECK_PROMPTS)]
    if pinned:
        outs, picks = routing.generate(engine, prompts, CHECK_GENERATED)
        pinned.clear()
        # the prompt and every token generated but the last, which is fed to nothing
        pinned.record([0] * CHECK_PROMPTS, [len(p) + len(o) - 1 for p, o in zip(prompts, outs)], picks)
    else:
        outs = engine.generate(prompts, max_new_tokens=CHECK_GENERATED)
    full = seqs.copy()
    for i, (p, o) in enumerate(zip(prompts, outs)):
        full[i, len(p):len(p) + len(o)] = o
    want = wanted(full)
    worst_gap = 0.0  # in units of the reference row's RMS
    for i, (p, o) in enumerate(zip(prompts, outs)):
        for j, tok in enumerate(o):
            row = want[i, len(p) + j - 1]
            worst_gap = max(worst_gap, float((row.max() - row[tok]) / np.sqrt(np.mean(row ** 2))))
    found = {"logit_rel_err": max(errs), "token_gap": worst_gap}
    said = dict(check_logit_rel_err=errs, tol=logit_tol, generated_token_gap=worst_gap,
                gap_tol=limits["token_gap"])
    if pinned:
        found["route_shortfall"] = pinned.shortfall
        said.update(check_route_shortfall=pinned.shortfall, shortfall_tol=limits["route_shortfall"],
                    check_flip_share=pinned.flips / pinned.audited)
    ok = bool(all(found[k] <= limits[k] for k in limits)
              and all(len(o) == CHECK_GENERATED for o in outs))
    harness.say(**said, ok=ok)
    return ok, {k: [found[k], limits[k]] for k in limits}


def warm(engine, workload, vocab):
    """One run of every program the window can reach: fused prefill at each
    (rows, chunk) the file lists, the decode chain at each row bucket."""
    rng = np.random.default_rng(0)
    w = workload["warm"]
    k = engine.config.decode_chain

    def prompts(n, length):
        return [rng.integers(0, vocab, length, dtype=np.int32) for _ in range(n)]

    for rows, chunk in w["prefill"]:
        engine.generate(prompts(rows, chunk), max_new_tokens=1)
    for rows in w["chain_rows"]:
        # the first token comes from the prefill, the next k from one chain
        engine.generate(prompts(rows, w["chain_prompt_len"]), max_new_tokens=1 + k)
    # The engine cuts each program's padded outputs down to its n live rows
    # eagerly (toks[:n], out[:n], emitted[:n]), one tiny compiled slice per
    # (rows, n). Make each once here, on arrays placed as the programs' outputs
    # are, or the first batch of every new size compiles inside the window.
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    placed = NamedSharding(engine.mesh, PartitionSpec())
    bucket = engine.config.row_bucket
    for rows in sorted({r for r, _ in w["prefill"]} | set(w["chain_rows"])):
        flat = jax.device_put(np.zeros((rows,), np.int32), placed)
        wide = jax.device_put(np.zeros((rows, k), np.int32), placed)
        for n in range(rows - bucket + 1, rows + 1):
            jax.block_until_ready((flat[:n], wide[:n]))


class Spans:
    """The benchmark's own spans around the engine's two dispatch methods,
    and the profiler switched on for a few seconds of the window."""

    def __init__(self, engine, trace_dir, start_after_s, trace_seconds):
        self.calls = []
        self.engine, self.traced = engine, harness.TraceWindow(trace_dir)
        self.start_after_s, self.trace_seconds = start_after_s, trace_seconds
        self.t0 = None
        self.state = "before"
        self.trace_started_s = None
        # the profiler's first start takes seconds: pay them here, in set-up
        self.traced.start()
        self.traced.stop()
        shutil.rmtree(trace_dir, ignore_errors=True)
        self._wrap("decode_chain", "decode_chain", self._chain_rows)
        self._wrap("_put_sample", "prefill", self._prefill_rows)

    def _chain_rows(self, args, out):
        uids, emitted = args[0], out[1]
        seen = np.asarray([self.engine.state.get(u).seen_tokens for u in uids]) - emitted
        context = float((emitted * seen + emitted * (emitted + 1) / 2).sum())
        return {"rows": len(uids), "row_steps": int(emitted.sum()), "context_tokens": context}

    def _prefill_rows(self, args, out):
        return {"rows": len(args[0]), "tokens": int(sum(len(t) for t in args[1]))}

    def _wrap(self, method, label, describe):
        import jax

        inner = getattr(self.engine, method)

        def wrapped(*args, **kwargs):
            self._switch()
            traced = self.state == "tracing"
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:" + label):
                out = inner(*args, **kwargs)
            self.calls.append({"kind": label, "t0": t0, "t1": time.perf_counter(),
                               "traced": traced, **describe(args, out)})
            return out

        setattr(self.engine, method, wrapped)

    def _switch(self):
        if self.t0 is None:
            return
        now = time.perf_counter() - self.t0
        if self.state == "before" and now >= self.start_after_s:
            self.trace_started_s = now
            self.traced.start()
            harness.say(profiler_start_s=time.perf_counter() - self.t0 - now)
            self.state, self.stop_at = "tracing", now + self.trace_seconds
        elif self.state == "tracing" and now >= self.stop_at:
            self.stop()

    def stop(self):
        self.traced.stop()
        self.state = "done"


def request_rows(records, outs, output_tokens, t_origin):
    rows = []
    for i, out in enumerate(outs):
        r = records[i]
        done = r.finish is not None and len(out) == output_tokens
        rows.append({
            "due_s": r.arrival - t_origin, "ok": done,
            "ttft_s": r.ttft_s, "queue_wait_s": r.queue_wait_s,
            "tpot_s": stats.tpot_s(r.first_token, r.finish, len(out)) if done else None,
            "finish_s": (r.finish - t_origin) if done else None,
            "tokens": len(out), "preemptions": r.preemptions})
    return rows


def run(*, workload, config, reference, architecture, seed, seconds, devices, trace_dir,
        compiles, t_process_start):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.topology.mesh import build_mesh

    tr = workload["traffic"]
    vocab = config["vocab_size"]
    model_cfg = program.model_config(config, jnp.bfloat16)
    phases = harness.Phases(t_process_start)
    params = make_weights(model_cfg, seed)
    mesh = build_mesh(devices=devices, axis_sizes={"tp": 1, "dp": len(devices)})
    engine = InferenceEngineV2(model_cfg, params, dict(workload["engine"]), mesh=mesh)
    del params
    phases.done("weights_and_engine")
    correct, compared = check(engine, reference, architecture, config, seed)
    phases.done("check_against_reference")
    warm(engine, workload, vocab)
    phases.done("warm_up")

    spans = Spans(engine, trace_dir, TRACE_START_SHARE * seconds, TRACE_SECONDS) if trace_dir else None
    compiles.mark()
    setup_s = time.perf_counter() - t_process_start
    t0 = time.perf_counter()
    if spans:
        spans.t0 = t0
    rows = []
    if tr["kind"] == "open_loop":
        reqs = traffic.open_loop(tr, vocab, seed, seconds)
        outs = engine.generate(reqs.prompts, max_new_tokens=reqs.output_tokens,
                               arrival_times=list(reqs.arrival_s), seed=seed & 0x7FFFFFFF)
        rows = request_rows(engine.lifecycle.records(), outs, reqs.output_tokens, t0)
    elif tr["kind"] == "closed_waves":
        wave_s = []
        for reqs in traffic.closed_waves(tr, vocab, seed):
            t_wave = time.perf_counter()
            if t_wave - t0 >= seconds:
                break
            outs = engine.generate(reqs.prompts, max_new_tokens=reqs.output_tokens,
                                   seed=seed & 0x7FFFFFFF)
            wave_s.append(time.perf_counter() - t_wave)
            rows += request_rows(engine.lifecycle.records(), outs, reqs.output_tokens, t0)
        # a run whose rate reads far off says here whether one wave was slow
        harness.say(waves=len(wave_s), wave_s=stats.describe(wave_s), longest_wave_s=max(wave_s))
    else:
        raise ValueError(f"the serve runner has no traffic kind {tr['kind']!r}")
    elapsed = time.perf_counter() - t0
    if spans:
        spans.stop()
    in_window = compiles.since_mark()
    memory = harness.memory_held(devices)

    ok = [r for r in rows if r["ok"]]
    failed = len(rows) - len(ok)
    first_due = min(r["due_s"] for r in rows)
    out_tokens = sum(r["tokens"] for r in ok)
    span_s = max(r["finish_s"] for r in ok) - first_due
    ttft_ms = [1e3 * r["ttft_s"] for r in ok]
    tpot_ms = [1e3 * r["tpot_s"] for r in ok]
    end_to_end = {
        "setup_s": setup_s,
        "serve_ttft_p95_ms": stats.percentile(ttft_ms, 95),
        "serve_tpot_p95_ms": stats.percentile(tpot_ms, 95),
        "serve_out_tokens_per_s": stats.rate(out_tokens, span_s),
    }
    harness.say(requests=len(rows), failed=failed, window_s=elapsed,
                ttft_ms=stats.describe(ttft_ms), ttft_p95_ms=end_to_end["serve_ttft_p95_ms"],
                tpot_ms=stats.describe(tpot_ms), tpot_p95_ms=end_to_end["serve_tpot_p95_ms"],
                out_tokens_per_s=end_to_end["serve_out_tokens_per_s"],
                preemptions=sum(r["preemptions"] for r in rows),
                compiles_in_window=in_window, setup_s=setup_s)
    return {
        "correct": bool(correct and failed == 0), "attempted": len(rows), "failed": failed,
        "end_to_end": end_to_end, "requests": rows, "compiles_in_window": in_window,
        "calls": spans.calls if spans else [], "chips": len(devices), "elapsed_s": elapsed,
        "trace_started_s": spans.trace_started_s if spans else None,
        "kv_pool_shape": tuple(engine.pool.k.shape), "memory": memory, "compared": compared,
    }
