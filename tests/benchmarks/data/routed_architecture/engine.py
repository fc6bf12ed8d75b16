"""A stand-in for the PROGRAM's half of the routed serving check: a small
routed decoder served in bf16 with a KV cache of its own, which reports the
experts it sent every token to. The program cannot run such a model yet
(PERF.md, section 7), so the benchmark's half (``runners/serve.py::check``,
the reference's contract) is proved on the CPU against this. It shares no code
with the reference beside it, has its own parameter tree, and takes the
planted faults of ``test_routed_check.py`` by name.

It offers what ``check`` uses of an engine: ``config.chunk_bucket``,
``params``, ``put``, ``flush``, ``generate``; and, for the architecture file's
``put_with_picks`` / ``generate_with_picks``, ``last_picks``: the picks of the
call just made, out of the same compiled step.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
FAULTS = ("down_proj_x1.25", "down_proj_x1.05", "weights_unnormalised", "scaling_left_out", "cache_e4m3",
          "ranks_2_to_k_plus_1", "picks_misreported", "bias_ignored")


@functools.partial(jax.jit, static_argnames=("spec",))
def make_params(key, spec):
    """Every leaf drawn from the seed in one call, in bf16: no routing
    parameter is left at zero, or it would be left unchecked."""
    cfg = dict(spec)
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    d, E, f = h // H, cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    keys = iter(jax.random.split(key, 1024))

    def normal(*shape, fan_in=None, scale=1.0):
        std = scale / np.sqrt(fan_in or shape[0])
        return (std * jax.random.normal(next(keys), shape)).astype(BF16)

    def norm_scale():
        return (1 + 0.1 * jax.random.normal(next(keys), (h,))).astype(BF16)

    def block(routed):
        out = {"ln_a": norm_scale(), "ln_m": norm_scale(),
               "q": normal(h, H, d), "k": normal(h, H, d), "v": normal(h, H, d),
               "o": normal(H, d, h, fan_in=h)}
        if not routed:
            F = cfg["intermediate_size"]
            return dict(out, gate=normal(h, F), up=normal(h, F), down=normal(F, h))
        return dict(out, route_w=normal(h, E), route_b=normal(E, fan_in=1, scale=0.1),
                    e_gate=normal(E, h, f, fan_in=h), e_up=normal(E, h, f, fan_in=h),
                    e_down=normal(E, f, h, fan_in=f),
                    sh_gate=normal(h, f), sh_up=normal(h, f), sh_down=normal(f, h))

    dense = cfg["first_k_dense_replace"]
    return {"tok_emb": normal(cfg["vocab_size"], h, fan_in=1), "head": normal(h, cfg["vocab_size"]),
            "ln_f": norm_scale(),
            "blocks": [block(i >= dense) for i in range(cfg["num_hidden_layers"])]}


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)).astype(BF16) * scale


def _rope(x, positions, theta):  # x [B, T, H, d], positions [B, T]
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * freq  # [B, T, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(BF16)


def _glu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _route(h, p, cfg, faults):
    """The k experts of every token and their weights: [B, T, k] each."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.einsum("bth,he->bte", h, p["route_w"],
                                       preferred_element_type=jnp.float32))
    select = scores if "bias_ignored" in faults else scores + p["route_b"].astype(jnp.float32)
    if "ranks_2_to_k_plus_1" in faults:
        picks = jax.lax.top_k(select, k + 1)[1][..., 1:]
    else:
        picks = jax.lax.top_k(select, k)[1]
    weights = jnp.take_along_axis(scores, picks, -1)
    if cfg["norm_topk_prob"] and "weights_unnormalised" not in faults:
        weights = weights / weights.sum(-1, keepdims=True)
    if "scaling_left_out" not in faults:
        weights = weights * cfg["routed_scaling_factor"]
    return picks.astype(jnp.int32), weights


def _experts(h, p, cfg, faults):
    picks, weights = _route(h, p, cfg, faults)
    E = p["route_w"].shape[-1]
    gate = (jax.nn.one_hot(picks, E, dtype=jnp.float32) * weights[..., None]).sum(-2).astype(BF16)
    down = p["e_down"]
    for fault in faults:
        if fault.startswith("down_proj_x"):
            # one expert of every routed layer, its output too large by the factor in the name. At 1.25
            # the check says so; at 1.05 the fault is about half of bf16's own error, and passes
            # (PERF.md, section 2)
            down = down.at[0].multiply(float(fault[len("down_proj_x"):]))
    hidden = jax.nn.silu(jnp.einsum("bth,ehf->btef", h, p["e_gate"])) \
        * jnp.einsum("bth,ehf->btef", h, p["e_up"])
    out = jnp.einsum("bte,btef,efh->bth", gate, hidden, down)
    return out + _glu(h, p["sh_gate"], p["sh_up"], p["sh_down"]), picks


@functools.partial(jax.jit, static_argnames=("spec", "faults"))
def step(params, cache, rows, tokens, start, n, spec, faults):
    """``tokens`` [B, T] (row b holds ``n[b]`` of them, the rest padding) at
    positions ``start[b]`` on, for the cache rows ``rows``: the logits at each
    row's last token, the cache with their keys and values written, and the
    picks [B, T, routed layers, k]."""
    cfg = dict(spec)
    B, T = tokens.shape
    positions = start[:, None] + jnp.arange(T)[None, :]
    x = params["tok_emb"][tokens]
    key_pos = jnp.arange(cache["k"].shape[2])
    picks = []
    new_k, new_v = [], []
    for layer, p in enumerate(params["blocks"]):
        h = _rms(x, p["ln_a"], cfg["rms_norm_eps"])
        q = _rope(jnp.einsum("bth,hnd->btnd", h, p["q"]), positions, cfg["rope_theta"])
        k = _rope(jnp.einsum("bth,hnd->btnd", h, p["k"]), positions, cfg["rope_theta"])
        v = jnp.einsum("bth,hnd->btnd", h, p["v"])
        if "cache_e4m3" in faults:
            k, v = (a.astype(jnp.float8_e4m3fn).astype(BF16) for a in (k, v))
        # a padding token's keys land past the row's last position, where no query looks
        # before a later call overwrites them
        keys = cache["k"][layer, rows].at[jnp.arange(B)[:, None], positions].set(k)
        values = cache["v"][layer, rows].at[jnp.arange(B)[:, None], positions].set(v)
        new_k.append(keys)
        new_v.append(values)
        att = jnp.einsum("btnd,bsnd->bnts", q, keys, preferred_element_type=jnp.float32)
        att = att / np.sqrt(q.shape[-1])
        att = jnp.where(key_pos[None, None, None, :] <= positions[:, None, :, None], att, -jnp.inf)
        ctx = jnp.einsum("bnts,bsnd->btnd", jax.nn.softmax(att, -1).astype(BF16), values)
        x = x + jnp.einsum("btnd,ndh->bth", ctx, p["o"])
        h = _rms(x, p["ln_m"], cfg["rms_norm_eps"])
        if "route_w" in p:
            out, chosen = _experts(h, p, cfg, faults)
            picks.append(chosen)
        else:
            out = _glu(h, p["gate"], p["up"], p["down"])
        x = x + out
    cache = {"k": cache["k"].at[:, rows].set(jnp.stack(new_k)),
             "v": cache["v"].at[:, rows].set(jnp.stack(new_v))}
    last = jnp.take_along_axis(x, (n - 1)[:, None, None], 1)[:, 0]
    logits = _rms(last, params["ln_f"], cfg["rms_norm_eps"]) @ params["head"]
    return logits, cache, jnp.stack(picks, 2)


class StandInEngine:
    def __init__(self, cfg, params, chunk_bucket=32, max_len=64, rows=8, faults=()):
        unknown = set(faults) - set(FAULTS)
        if unknown:
            raise ValueError(f"no planted fault {sorted(unknown)}")
        self.config = types.SimpleNamespace(chunk_bucket=chunk_bucket)
        self.params, self.faults = params, frozenset(faults)
        self.spec = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, bool))))
        H = cfg["num_attention_heads"]
        shape = (cfg["num_hidden_layers"], rows, max_len, H, cfg["hidden_size"] // H)
        self.cache = {"k": jnp.zeros(shape, BF16), "v": jnp.zeros(shape, BF16)}
        self.free, self.seen = list(range(rows)), {}
        self.last_picks = None

    def put(self, uids, token_lists):
        """Last-position logits [rows, V] of ``token_lists[i]`` appended to sequence ``uids[i]``."""
        for uid in uids:
            if uid not in self.seen:
                self.seen[uid] = (self.free.pop(0), 0)
        n = np.asarray([len(t) for t in token_lists], np.int32)
        width = self.config.chunk_bucket if n.max() > 1 else 1
        tokens = np.zeros((len(uids), width), np.int32)
        for i, t in enumerate(token_lists):
            tokens[i, :len(t)] = t
        rows, start = (np.asarray(a, np.int32) for a in zip(*(self.seen[u] for u in uids)))
        logits, self.cache, picks = step(self.params, self.cache, rows, tokens, start, n,
                                         spec=self.spec, faults=self.faults)
        picks = np.asarray(picks)
        if "picks_misreported" in self.faults:
            picks = (picks + 1) % dict(self.spec)["n_routed_experts"]
        self.last_picks = [picks[i, :n[i]] for i in range(len(uids))]
        for u, count in zip(uids, n):
            self.seen[u] = (self.seen[u][0], self.seen[u][1] + int(count))
        return np.asarray(logits, np.float32)

    def flush(self, uid):
        self.free.append(self.seen.pop(uid)[0])

    def generate(self, prompts, max_new_tokens):
        """Greedy tokens; ``last_picks[i]`` then covers every token fed for
        prompt i: the prompt and all it generated but the last."""
        uids = [("generate", i) for i in range(len(prompts))]
        logits, picks = self.put(uids, prompts), self.last_picks
        outs = [[int(row.argmax())] for row in logits]
        for _ in range(max_new_tokens - 1):
            logits = self.put(uids, [np.asarray(o[-1:], np.int32) for o in outs])
            picks = [np.concatenate([a, b]) for a, b in zip(picks, self.last_picks)]
            for o, row in zip(outs, logits):
                o.append(int(row.argmax()))
        for uid in uids:
            self.flush(uid)
        self.last_picks = picks
        return [np.asarray(o, np.int32) for o in outs]
