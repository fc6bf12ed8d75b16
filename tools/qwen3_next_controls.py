#!/usr/bin/env python3
"""The planted faults that the ``qwen3_next`` cell's ``check`` has to refuse,
run through ``benchmarks/run.py`` itself on the chip (the readings behind
``check.readings.*.control_min`` of ``benchmarks/configs/qwen3-next-80b-a3b.json``),
and the decode over the traffic's own length that the runner's check, which
decodes 12 tokens, does not reach.

    python3 tools/qwen3_next_controls.py --control <one of CONTROLS> \\
        --workload qwen3-next-80b-a3b.serve.long-output-wave128 --seed <n> --seconds 5 --trace 0
    python3 tools/qwen3_next_controls.py --control drift --workload qwen3-next-80b-a3b.serve.long-output-wave128 --seed <n>
    python3 tools/qwen3_next_controls.py --control where [--variant bf16_mixer] --workload <the same> --seed <n>

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.
The reference always runs the configuration as it is written.

- ``e4m3_out_proj`` (the nearest precision below the bf16 the WEIGHTS are kept
  in): every DeltaNet mixer's ``gdn_out_proj`` goes into the engine through
  float8_e4m3fn, planted on the host (``tools/routed_controls.py::plant_e4m3``);
  the reference is given the weights as they were.
- ``beta_1``: the delta rule writes with ``beta = 1`` for ``sigmoid(b)``.
- ``g_0``: the state never decays (``g = 0``).
- ``no_l2norm``: ``q`` and ``k`` are not normalised (``q`` keeps its scale).
- ``no_attn_gate``: the attention's output is not multiplied by ``sigmoid(gate)``.
- ``w_for_1_plus_w``: every norm multiplies by ``w`` where the configuration says ``1 + w``.
- ``rotary_whole_head``: the rotary turns all 256 dimensions of a head, not the first 64.
- ``no_shared_gate``: the shared expert is added unweighted.
- ``renorm_held_only``: the picks' weights are renormalised over the picks HELD
  on this chip (as if the chip were the whole layer), not over all ten.
- ``pad_moves_state``: the tokens a prompt is padded with are left to move the
  state (``gdn_chunked`` is not told which tokens are live).
- ``ranks_2_to_k1``: the router takes ranks 2..k+1 of its scores for 1..k: picks
  that this router could not have made (the audit's control: ``route_shortfall``).
- ``bf16_state``: every state a layer writes to the pool goes through bfloat16:
  kept as a reading of what the check CANNOT see (two tokens after a prompt).

Of these the last line is ``run.py``'s: ``correct`` has to read false (but for
``bf16_state``).

- ``drift``: 8 prompts of the traffic's lengths through the fused prefill and
  then 511 tokens of decode chains (the timed path's own greedy tokens, the
  chain ahead); the last token is fed through ``put`` and its logits, which
  rest on every state update before them, are compared with the reference's
  FULL forward of the same continuation at the program's own picks, as is
  every token generated (its gap under the reference's best logit). One JSON
  line; ``ok`` by the configuration's own tolerances.
- ``where``: WHERE the audit's ``route_shortfall`` is large. The runner's own
  two passes (``put``: a prompt, then two tokens through the slot and the pages;
  ``generate``: the fused prefill and the chain) over 8 prompts with the audit's
  whole ``[rows, positions, layers]`` array kept: its largest and 99.9th
  percentile by layer and by position, the twelve largest positions one by one,
  those positions fed again as prompts of their own (their last logits against
  the reference's), prompts of 1-65 tokens likewise, and the reference's OWN
  conditioning there: the same pinned pass with every embedding row moved by
  one bfloat16 rounding's worth, and how far each position's logits and
  shortfall move. ``--variant bf16_mixer`` runs it on the program with a
  DeltaNet mixer's ``[q | k | v | z]`` rounded to bfloat16 as they leave the
  in-projection (this PR's first form; ``ops/gdn.py`` says why it is not the
  one that ships). Writes ``chiprun_out/where_<sound | variant>_<seed>.json``.
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = ("e4m3_out_proj", "beta_1", "g_0", "no_l2norm", "no_attn_gate", "w_for_1_plus_w", "rotary_whole_head",
            "no_shared_gate", "renorm_held_only", "pad_moves_state", "ranks_2_to_k1", "bf16_state")
DRIFT_ROWS, DRIFT_TOKENS = 8, 512
WHERE_ROWS = 8
VARIANTS = ("bf16_mixer",)


def plant_rule_inputs(control):
    """``ops/gdn.py::rule_inputs`` hands the rule ``(q, k, v, g, beta)`` with one of them changed."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops import gdn

    honest = gdn.rule_inputs

    def rule_inputs(qkv, ba, A_log, dt_bias, sizes):
        q, k, v, g, beta = honest(qkv, ba, A_log, dt_bias, sizes)
        if control == "beta_1":
            beta = jnp.ones_like(beta)
        elif control == "g_0":
            g = jnp.zeros_like(g)
        else:  # no_l2norm
            width = sizes.n_k_heads * sizes.head_k_dim
            q = (qkv[..., :width].astype(jnp.float32) * sizes.head_k_dim ** -0.5).astype(q.dtype).reshape(q.shape)
            k = qkv[..., width:2 * width].reshape(k.shape)
        return q, k, v, g, beta

    gdn.rule_inputs = rule_inputs


def plant_no_attn_gate():
    """The gate half of every head's query projection reads +30: ``sigmoid`` 1."""
    from deepspeed_tpu.inference import paged

    honest = paged._qkv

    def qkv(lp, cfg, x):
        q, k, v = honest(lp, cfg, x)
        return q.at[..., v.shape[-1]:].set(30.0), k, v

    paged._qkv = qkv


def plant_plain_norm_scale():
    from deepspeed_tpu.models import transformer

    honest = transformer._apply_norm
    transformer._apply_norm = lambda p, cfg, x: honest(p, dataclasses.replace(cfg, norm_unit_offset=False), x)


def plant_no_shared_gate():
    from deepspeed_tpu.inference import paged

    honest = paged._moe_with_picks
    paged._moe_with_picks = lambda lp, cfg, x: honest({k: v for k, v in lp.items() if k != "shared_gate"}, cfg, x)


def plant_renorm_held_only():
    import jax.numpy as jnp

    from deepspeed_tpu.inference import model

    honest = model._experts

    def experts(ep, cfg, tokens, top_p, top_i):
        local = top_i - cfg.first_expert
        mine = jnp.where((local >= 0) & (local < cfg.num_experts), top_p, 0.0)
        return honest(ep, cfg, tokens, mine / jnp.maximum(mine.sum(-1, keepdims=True), 1e-9), top_i)

    model._experts = experts


def plant_pad_moves_state():
    from deepspeed_tpu.ops import gdn

    honest = gdn.gdn_chunked
    gdn.gdn_chunked = lambda q, k, v, g, beta, chunk, initial_state=None, live=None: honest(
        q, k, v, g, beta, chunk, initial_state, None)


def plant_router():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.parallel import moe

    def route(logits, top_k, *, kind="softmax", bias=None, renormalize=True, scale=1.0):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, picks = (a[:, 1:] for a in jax.lax.top_k(probs, top_k + 1))
        return weights / weights.sum(-1, keepdims=True) * scale, picks.astype(jnp.int32)

    moe.route = route


def plant_state_dtype(dtype):
    import jax.numpy as jnp

    from deepspeed_tpu.ops import gdn

    step, put = gdn.gdn_pool_step, gdn.put_pool_rows

    def rounded(states):
        return states.astype(dtype).astype(jnp.float32)

    def pool_step(pool, layer, q, k, v, *args, **kw):
        o, pool = step(pool, layer, q, k, v, *args, **kw)
        row = gdn.PoolRow(pool, layer, jnp.zeros(v.shape[:1], bool))
        return o, put(row, rounded(gdn.pool_rows(row, v.shape[0])))

    gdn.gdn_pool_step = pool_step
    gdn.put_pool_rows = lambda row, states: put(row, rounded(states))


def plant_variant(variant):
    """The program with one part computed more coarsely than it ships, to see what a reading rests on."""
    from deepspeed_tpu.inference import paged

    assert variant == "bf16_mixer"  # a DeltaNet mixer's [q | k | v | z] rounded to bfloat16 as they leave the
    dense = paged._dense            # in-projection: the convolution, q and k's norm and the chunk's products follow

    def rounded(lp, key, cfg, x, einsum=None, sums=None):
        return dense(lp, key, cfg, x, einsum, sums).astype(cfg.dtype)

    paged._dense = rounded


def cell(workload_name: str, seed: int):
    """The cell's engine on the chip with weights from ``seed``, and what the harness compares it with."""
    import jax.numpy as jnp

    from benchmarks.lib import harness, program
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.topology.mesh import build_mesh

    workload = harness.load_workload(workload_name)
    config = harness.load_config(workload["config"])
    devices = harness.require_devices(1)
    harness.enable_compile_cache()
    runner = harness.load_runner("serve")
    reference = harness.load_reference(config["architecture"])
    architecture = harness.load_architecture(config["architecture"])
    model_cfg = program.model_config(config, jnp.bfloat16)
    engine = InferenceEngineV2(model_cfg, runner.make_weights(model_cfg, seed), dict(workload["engine"]),
                               mesh=build_mesh(devices=devices, axis_sizes={"tp": 1, "dp": 1}))
    return workload, config, devices, runner, reference, architecture, engine


def drift(workload_name: str, seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import program

    workload, config, devices, _, reference, architecture, engine = cell(workload_name, seed)
    routing = program.routing(architecture, config)
    cfg, weights = program.published(config), architecture.reference_weights(engine.params)
    # (the rows are taken out on the host: a reference may hand its logits to the host's memory as it makes them)
    run = jax.jit(lambda w, t, p: (reference.forward(w, cfg, t, p), reference.route_shortfall(w, cfg, t, p)))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 11])
    lo, hi = workload["traffic"]["prompt_len"]["min"], workload["traffic"]["prompt_len"]["max"]
    prompts = [rng.integers(0, config["vocab_size"], int(n), dtype=np.int32)
               for n in rng.integers(lo, hi + 1, DRIFT_ROWS)]
    uids = list(range(50_000, 50_000 + DRIFT_ROWS))
    # the timed path's own programs (the fused prefill, then chains kept ahead); the sequences stay
    # resident so that the last token's logits rest on every state update, and the picks are kept
    key = jax.device_put(jax.random.PRNGKey(0), engine._replicated)
    greedy = (("do_sample", False), ("temperature", 1.0), ("top_k", 0), ("top_p", 1.0))
    engine.picks_log = []
    first, key = engine._put_sample(uids, prompts, key, greedy)
    again = [list(p) + [int(t)] for p, t in zip(prompts, first)]
    left, k = DRIFT_TOKENS - 2, engine.config.decode_chain
    while left > 0:
        out, emitted, key = engine.decode_chain(uids, [s[-1] for s in again], [left] * DRIFT_ROWS, k, key,
                                                sample_kw=greedy, ahead=left > k)
        for s, row, n in zip(again, out, emitted):
            s.extend(int(t) for t in row[:n])
        left -= int(emitted[0])
    logits = np.asarray(engine.put(uids, [np.asarray(s[-1:], np.int32) for s in again]), np.float32)
    resident = engine._picks_by_request(DRIFT_ROWS)
    engine.picks_log = None
    errs, worst_gap, worst_shortfall, flips, audited = [], 0.0, -np.inf, 0, 0
    longest = hi + DRIFT_TOKENS  # one shape for the reference: a row is padded behind its last token (causal)
    for p, s, got, pk in zip(prompts, again, logits, resident):
        n = len(s)
        padded = np.zeros((1, longest), np.int32)
        padded[0, :n] = s
        all_picks = np.broadcast_to(np.arange(routing.k, dtype=np.int32), (1, longest, routing.layers, routing.k)).copy()
        all_picks[0, :n] = program.checked_picks(pk, n, routing)
        want, shortfall = (np.asarray(a)[0, :n] for a in run(weights, jnp.asarray(padded), jnp.asarray(all_picks)))
        errs.append(program.relative_error(got, want[-1]))
        worst_shortfall = max(worst_shortfall, float(shortfall.max()))
        flips, audited = flips + int((shortfall > 0).sum()), audited + shortfall.size
        for pos in range(len(p), len(s)):
            row = want[pos - 1]
            worst_gap = max(worst_gap, float((row.max() - row[s[pos]]) / np.sqrt(np.mean(row ** 2))))
    tol = program.tolerance(config, "logit_rel_tol")
    short_tol = program.tolerance(config, "route_shortfall_tol")
    ok = (max(errs) <= tol and worst_shortfall <= short_tol
          and all(len(s) - len(p) == DRIFT_TOKENS - 1 for p, s in zip(prompts, again)))
    print(json.dumps({"ok": bool(ok), "control": "drift", "rows": DRIFT_ROWS, "decoded": DRIFT_TOKENS,
                      "context": [len(s) for s in again], "drift_logit_rel_err": errs, "tol": tol,
                      "route_shortfall": worst_shortfall, "shortfall_tol": short_tol, "flip_share": flips / audited,
                      "token_gap": worst_gap, "chains_ahead": engine.chains_ahead, "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


def shortfall_stats(shortfall, fed, lens):
    """Where the audit's readings lie: ``shortfall`` [B, S, layers] over the positions ``fed`` [B, S]
    of rows whose prompts are ``lens`` long, by layer, by position and the largest one by one."""
    import numpy as np

    B, S, L = shortfall.shape
    rows, pos = np.nonzero(fed)
    vals = shortfall[rows, pos]  # [positions, layers]

    def said(v):
        v = v.ravel()
        return {"n": int(v.size), "max": float(v.max()), "q999": float(np.quantile(v, 0.999)),
                "q99": float(np.quantile(v, 0.99)), "flip_share": float((v > 0).mean())}

    by_layer = [said(vals[:, j]) for j in range(L)]
    edges = [0, 1, 2, 4, 8, 16, 32, 64, 128, 192, S]
    decoded = pos >= lens[rows]
    by_position = {f"{a}-{b - 1}": said(vals[(pos >= a) & (pos < b) & ~decoded])
                   for a, b in zip(edges, edges[1:]) if ((pos >= a) & (pos < b) & ~decoded).any()}
    if decoded.any():
        by_position["past_the_prompt"] = said(vals[decoded])
    order = np.argsort(vals.max(-1))[::-1][:12]
    largest = [{"row": int(rows[i]), "pos": int(pos[i]), "prompt": int(lens[rows[i]]),
                "layer": int(vals[i].argmax()), "shortfall_by_layer": [round(float(x), 4) for x in vals[i]]}
               for i in order]
    hist = np.histogram(vals[vals > 0], bins=[0, .02, .05, .1, .15, .2, .3, .4, .5, .75, 1, 10])[0]
    return {"all": said(vals), "by_layer": by_layer, "by_position": by_position, "largest": largest,
            "positive_by_size": [int(c) for c in hist]}


def where(workload_name: str, seed: int) -> int:
    """The harness's own two passes (``put``: a prompt, then two tokens through the state slot and
    the pages; ``generate``: the fused prefill and the decode chain) over WHERE_ROWS prompts, the
    audit's whole array kept, and the positions it is largest at fed again alone, so that their own
    last-position logits say whether the residual stream is off there or the router alone."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import program

    workload, config, devices, runner, reference, architecture, engine = cell(workload_name, seed)
    routing = program.routing(architecture, config)
    cfg, weights = program.published(config), architecture.reference_weights(engine.params)
    bucket = engine.config.chunk_bucket
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 13])
    lens = rng.integers(bucket // 2, bucket - runner.CHECK_DECODE_STEPS, WHERE_ROWS)
    seqs = rng.integers(0, config["vocab_size"], (WHERE_ROWS, bucket + runner.CHECK_GENERATED), dtype=np.int32)
    pinned = runner.Pinned(reference, routing, cfg, weights, seqs.shape)

    def audit(tokens, w=weights):
        want, shortfall = pinned.run(w, jnp.asarray(tokens), jnp.asarray(pinned.picks))
        return np.asarray(want), np.asarray(shortfall)

    # the reference's OWN conditioning: the same pass with every embedding row moved by one bf16 rounding's worth
    embed = np.asarray(weights["embed"].astype(jnp.float32))
    moved = dict(weights, embed=jnp.asarray(embed * (1 + 2.0 ** -8 * rng.uniform(-1, 1, embed.shape).astype(np.float32))))

    def fed_alone(rows, ends, uid0):
        """Row ``rows[i]``'s first ``ends[i]`` tokens as a request of its own: the relative error of its last logits."""
        uids = list(range(uid0, uid0 + len(rows)))
        fed = [seqs[r, :n] for r, n in zip(rows, ends)]
        logits, picks = routing.put(engine, uids, fed)
        for uid in uids:
            engine.flush(uid)
        pinned.clear()
        pinned.record([0] * len(rows), [len(f) for f in fed], picks)
        tokens = np.zeros_like(seqs)
        for i, f in enumerate(fed):
            tokens[i, :len(f)] = f
        want, _ = audit(tokens)
        return [program.relative_error(np.asarray(logits[i], np.float32), want[i, n - 1]) for i, n in enumerate(ends)]

    out = {"control": "where", "seed": seed, "rows": WHERE_ROWS, "prompts": [int(n) for n in lens],
           "device": devices[0].device_kind}
    uids = list(range(60_000, 60_000 + WHERE_ROWS))
    got = []
    for step in range(runner.CHECK_DECODE_STEPS + 1):
        starts = [0 if step == 0 else lens[i] + step - 1 for i in range(WHERE_ROWS)]
        fed = [seqs[i, starts[i]:lens[i] + step] for i in range(WHERE_ROWS)]
        logits, picks = routing.put(engine, uids, fed)
        pinned.record(starts, [len(f) for f in fed], picks)
        got.append(np.asarray(logits, np.float32))
    for uid in uids:
        engine.flush(uid)
    want, shortfall = audit(seqs)
    out["put"] = shortfall_stats(shortfall, pinned.fed, lens)
    want_moved, shortfall_moved = audit(seqs, moved)
    rows, pos = np.nonzero(pinned.fed)
    by = np.linalg.norm(want_moved - want, axis=-1)[rows, pos] / np.linalg.norm(want, axis=-1)[rows, pos]
    shift = np.abs(shortfall_moved - shortfall)[rows, pos].max(-1)  # [positions]: the largest over the layers
    at = {(r, p): i for i, (r, p) in enumerate(zip(rows.tolist(), pos.tolist()))}
    top = [at[(w["row"], w["pos"])] for w in out["put"]["largest"]]
    out["reference_moved_by_a_rounding"] = {
        "logits_moved": {"q50": float(np.quantile(by, .5)), "q99": float(np.quantile(by, .99)), "max": float(by.max()),
                         "at_the_largest": [float(by[i]) for i in top]},
        "shortfall_moved": {"q50": float(np.quantile(shift, .5)), "q99": float(np.quantile(shift, .99)),
                            "max": float(shift.max()), "at_the_largest": [float(shift[i]) for i in top]},
        "rank_of_the_largest_among_positions_by_logits_moved": [int((by > by[i]).sum()) for i in top],
        "positions": int(by.size)}
    out["put"]["logit_rel_err_by_row"] = [[program.relative_error(got[step][i], want[i, lens[i] + step - 1])
                                           for i in range(WHERE_ROWS)] for step in range(len(got))]
    worst = out["put"]["largest"][:WHERE_ROWS]

    prompts = [seqs[i, :lens[i]] for i in range(WHERE_ROWS)]
    outs, picks = routing.generate(engine, prompts, runner.CHECK_GENERATED)
    pinned.clear()
    pinned.record([0] * WHERE_ROWS, [len(p) + len(o) - 1 for p, o in zip(prompts, outs)], picks)
    full = seqs.copy()
    for i, (p, o) in enumerate(zip(prompts, outs)):
        full[i, len(p):len(p) + len(o)] = o
    out["generate"] = shortfall_stats(audit(full)[1], pinned.fed, lens)

    # one row bucket, one chunk bucket: the warm check's own programs
    out["largest_fed_alone"] = dict(
        at=[[w["row"], w["pos"]] for w in worst],
        logit_rel_err=fed_alone([w["row"] for w in worst], [w["pos"] + 1 for w in worst], 61_000))
    short = [1, 2, 3, 4, 8, 16, 64, 65][:WHERE_ROWS]
    out["short_prompts"] = dict(tokens=short, logit_rel_err=fed_alone(list(range(len(short))), short, 62_000))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/where_{os.environ.get('WHERE_TAG', 'sound')}_{seed}.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=CONTROLS + ("drift", "where"))
    ap.add_argument("--variant", choices=VARIANTS, help="with --control where: the program with one part made coarser")
    args, rest = ap.parse_known_args()
    if args.control in ("drift", "where"):
        run = argparse.ArgumentParser()
        run.add_argument("--workload", required=True)
        run.add_argument("--seed", type=int, default=0)
        asked, _ = run.parse_known_args(rest)
        if args.control == "drift":
            return drift(asked.workload, asked.seed)
        if args.variant:
            plant_variant(args.variant)
            os.environ["WHERE_TAG"] = args.variant
        return where(asked.workload, asked.seed)
    if args.control == "e4m3_out_proj":
        import routed_controls

        routed_controls.plant_e4m3(lambda path: "'gdn_out_proj'" in path)
    elif args.control in ("beta_1", "g_0", "no_l2norm"):
        plant_rule_inputs(args.control)
    elif args.control == "no_attn_gate":
        plant_no_attn_gate()
    elif args.control == "w_for_1_plus_w":
        plant_plain_norm_scale()
    elif args.control == "rotary_whole_head":
        from granite_controls import plant_config  # the program built from the configuration with a field replaced

        plant_config(rotary_dim=None)
    elif args.control == "no_shared_gate":
        plant_no_shared_gate()
    elif args.control == "renorm_held_only":
        plant_renorm_held_only()
    elif args.control == "pad_moves_state":
        plant_pad_moves_state()
    elif args.control == "ranks_2_to_k1":
        plant_router()
    else:
        import jax.numpy as jnp

        plant_state_dtype(jnp.bfloat16)
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
