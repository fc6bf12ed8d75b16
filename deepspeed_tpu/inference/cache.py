"""What a model caches of a sequence: the one module that knows.

Pages of keys and values a block of positions, one latent slab a token (and an
index key beside it under a learned indexer), summary and window pages under
EVA attention, a ring of pages a sliding layer beside the pages that grow, one
slot of recurrent state a Mamba-2 or Gated DeltaNet layer: which of them a
model keeps is said ONCE, as a :class:`CachePlan` made from its config alone
(:func:`cache_plan`): the classes of page a row holds, the row's table layout,
the state slot, their bytes, what the kind does not serve with, and the arrays
(:class:`Pools`, the one thing every step program is handed). The device side
(``paged.py``) and the host side (``ragged.py``) import from here and neither
from the other; the engine makes the plan and names no kind. A new kind of
cache edits this module and adds its attention builder (``paged._ATTENTION``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.transformer import TransformerConfig, sliding_kind
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.utils.hbm import kv_slot_bytes


# ------------------------------------------------------------ a row's table
class _Layout:
    """What the host's allocator (``ragged.StateManager``) asks of a row's block
    table, whatever the model: two groups of columns, the first ``summary_cols``
    and then ``window_pages`` more, whose pages come from the classes
    ``classes`` names (an allocator a class); ``pages(seen, new)`` of each a row
    must hold while ``new`` tokens are fed after ``seen``; ``capacity``, the
    tokens a row may hold; ``closed(before, after)``, what goes behind a row
    that moved on; ``check_fed``, what a call may not feed."""

    classes = (0, 0)  # the class of page of each group of columns
    one_token_program = False  # a call of single tokens runs a ``(rows, 1)`` program, whatever the chunk bucket

    @property
    def width(self) -> int:
        return self.summary_cols + self.window_pages

    @property
    def capacity(self) -> int:
        return self.max_seq_len

    def closed(self, before: int, after: int) -> Tuple[int, int]:
        """(windows a row closed on its way from ``before`` tokens to ``after``, the second group's pages it
        keeps then: the rest go back to their allocator). Nothing, but under a :class:`WindowLayout`."""
        return 0, 0

    def check_fed(self, uids, lens, seen) -> None:
        """Raise on what a call may not feed (``lens`` tokens a row after ``seen``, numpy arrays)."""


@dataclasses.dataclass(frozen=True)
class PlainLayout(_Layout):
    """The table of a row with one class of page and no window: a block of positions a column."""

    block_size: int
    max_seq_len: int
    window_pages = 0

    @property
    def summary_cols(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def capacity(self) -> int:
        return self.summary_cols * self.block_size  # (whole pages: what the columns hold)

    def pages(self, seen: int, new: int) -> Tuple[int, int]:
        return -(-(seen + new) // self.block_size), 0


@dataclasses.dataclass(frozen=True)
class WindowLayout(_Layout):
    """An EVA row's block table (``paged._eva_attention`` is the device's
    reading of the same columns): ``summary_cols`` columns of summary pages,
    ``per_closed`` a closed window in the windows' order, then
    ``window_pages`` columns for the open window's exact rows, position ``t``
    in page ``(t % window) // block_size``. A page of exact
    rows pools into one summary row (``block_size`` is the model's chunk), so
    a closing adds ``per_closed`` pages and frees the window's own behind it.
    """

    window: int
    block_size: int
    max_seq_len: int

    @property
    def window_pages(self) -> int:
        return self.window // self.block_size

    @property
    def per_closed(self) -> int:
        return self.window // self.block_size // self.block_size

    @property
    def summary_cols(self) -> int:
        return -(-self.max_seq_len // self.window) * self.per_closed

    def pages(self, seen: int, new: int) -> Tuple[int, int]:
        """(summary pages, window pages) a row must hold while ``new`` tokens
        are fed to it after ``seen``. A fresh row's tokens are a chunk, which
        stores the summaries of the windows it closes and the rows of the one
        it leaves open; after that tokens come one at a time (a decode chain's
        steps) and fill the open window to its end before the next one writes
        over its pages."""
        chunk = new > 1 and not seen
        rows = new % self.window if chunk else min(seen % self.window + new, self.window)
        return (seen + new) // self.window * self.per_closed, -(-rows // self.block_size)

    def attended(self, position):
        """Rows the token at ``position`` (a number or an array of them)
        attends to: the summaries of the closed windows and its own window's
        rows up to itself."""
        return (position // self.window * (self.window // self.block_size)
                + position % self.window + 1)

    def closed(self, before: int, after: int) -> Tuple[int, int]:
        """The pooled rows are summaries now, and the open window holds only what came after."""
        return after // self.window - before // self.window, -(-(after % self.window) // self.block_size)

    def check_fed(self, uids, lens, seen) -> None:
        if ((lens > 1) & (seen > 0)).any():
            i = int(np.argmax((lens > 1) & (seen > 0)))
            raise ValueError(
                f"uid {uids[i]}: a chunk of {int(lens[i])} tokens after {int(seen[i])}: with EVA "
                "attention a chunk of more than one token starts a sequence (the chunk path does not "
                "read earlier windows' summaries from the pool); feed the whole context at once, or "
                "one token at a time")


def ring_columns(window: int, block_size: int) -> int:
    """Pages that hold ``window`` consecutive positions wherever they start: the ring a row keeps of a sliding
    layer (``RingLayout`` on the host, ``paged._windowed_attention`` on the device)."""
    return -(-window // block_size) + 1


@dataclasses.dataclass(frozen=True)
class RingLayout(_Layout):
    """A row's block table under a sliding kind (``Pools.ring`` and
    ``paged._windowed_attention`` are the device's reading of the same
    columns): ``summary_cols`` GLOBAL columns, one a block of positions, whose
    pages are the full-attention layers' and grow with the context, then
    ``window_pages`` RING columns, whose pages are the sliding layers': block
    ``b`` in ring column ``b % window_pages``, written over when block ``b +
    window_pages`` arrives. The two classes of page come from two free lists
    (``StateManager.allocators``) and index two arrays.
    A row takes ring pages as its context grows to a window and keeps them to
    its flush: nothing is freed behind a ring and nothing moves.

    The names ``summary_cols`` / ``window_pages`` are :class:`WindowLayout`'s for
    the same two places of a row's table, so ``ragged.SequenceDescriptor`` holds
    every layout with one set of fields."""

    window: int
    block_size: int
    max_seq_len: int
    classes = (0, 1)
    # a call of single tokens reads the ring and the table; a wider call attends inside its chunks alone
    one_token_program = True

    @property
    def window_pages(self) -> int:
        """Ring columns (``ring_columns``)."""
        return ring_columns(self.window, self.block_size)

    @property
    def summary_cols(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    def pages(self, seen: int, new: int) -> Tuple[int, int]:
        """(global pages, ring pages) a row holds once ``new`` tokens are fed after ``seen``."""
        blocks = -(-(seen + new) // self.block_size)
        return blocks, min(blocks, self.window_pages)

    def overwritten(self, seen: int, new: int) -> int:
        """Ring pages that ``new`` tokens after ``seen`` start writing over: the
        blocks they open past the ring's first round."""
        past = [max(-(-n // self.block_size) - self.window_pages, 0) for n in (seen, seen + new)]
        return past[1] - past[0]

    def check_fed(self, uids, lens, seen) -> None:
        if lens.max(initial=0) > 1 and (seen > 0).any():
            i = int(np.argmax(seen > 0))
            raise ValueError(
                f"uid {uids[i]}: {int(lens[i])} token(s) after {int(seen[i])} in a call that feeds a chunk: with a "
                "sliding kind a call of more than one token a row takes fresh prompts alone (a fresh prompt attends "
                "inside the chunk and writes its last window; a row past position 0 would have to read the ring and "
                "the global pages: ROADMAP R3b); feed the whole context at once, or one token a row in a call")


# ------------------------------------------------------------ the arrays
class PagedKVPool(NamedTuple):
    """k/v: ``[L*NB, bs, kvH*hd]`` page-major pool, layer ``l``'s block ``b``
    at row ``l*NB + b`` (reference: FastGen preallocates the KV arena up front
    from a memory budget, ``DSStateManager`` + ``KVCacheConfig``). Row-major
    this is the byte order of ``[L, NB*bs, kvH, hd]``; what the shape fixes is
    the TPU's tiling: the two minor dims ``(bs, kvH*hd)`` are a page, so a
    page is addressed by the leading index alone and the kernel takes the
    array as it is. The layers are NOT a dimension of their own: merging
    ``[L, NB]`` is free for the values but re-lays the scales out (their
    tiled second-minor dim would be ``NB``). This NamedTuple is a jit pytree
    and holds only arrays; ``L`` comes from the model config.

    Quantized storage (``kv_quant='int8'|'fp8'``): k/v hold int8/e4m3 values
    and ``k_scale``/``v_scale`` carry one fp32 scale per (layer, slot, kv-head)
    — the quantization block is the ``hd`` head vector, so a token's KV write
    is one ``ops.quant`` block-math call and dequant needs only the slot's own
    scale (fused into the paged-attention block loads). A page's scales are
    ONE lane-dense row ``[bs*kvH]`` (slot-major, the values' own order): fp32
    with a minor dim of ``kvH`` alone would pad to 128 lanes on the TPU.
    ``None`` scales mean a full-precision pool.

    A LATENT pool (latent attention, ``TransformerConfig.kv_lora_rank > 0``)
    is ``k`` alone, ``[L*NB, bs, W]``, and ``v`` is ``None``: a token's row is
    one slab shared by every head, ``[latent after its norm | rotary key after
    RoPE | zeros]``, ``W = latent_pool_width(cfg)`` = rank + rope width
    rounded up to whole 128-lane tiles (512 + 64 -> 640: a minor dim of 576
    pads to 640 in HBM either way, so the padding is said, not hidden). The
    keys are the slab, the values its first ``kv_lora_rank`` columns; nothing
    else of a token is cached. It has no quantized form. Under a learned
    indexer (``TransformerConfig.index_topk > 0``) ``v`` is the INDEX pool,
    ``[L*NB, bs, index_pool_width(cfg)]``: a token's index key after its norm
    and RoPE, in the page and slot its latent has, so one block table a row
    places everything a token caches and the two arrays are read by two
    different kernels, each the bytes it needs."""

    k: jax.Array
    v: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None  # [L*NB, bs*kvH] fp32, or None
    v_scale: Optional[jax.Array] = None

    @property
    def block_size(self) -> int:
        return self.k.shape[1]

    @property
    def quant(self) -> Optional[str]:
        """Storage quantization mode, derived from the value dtype (trace-time
        static): None | 'int8' | 'fp8'."""
        if self.k_scale is None:
            return None
        return "fp8" if self.k.dtype == jnp.float8_e4m3fn else "int8"


class StatePool(NamedTuple):
    """What the state-space layers of a layer pattern (``TransformerConfig.
    layer_types``) keep of a sequence, beside the page pool that its attention
    layers write: not a row a token but ONE slot a sequence a layer, whatever
    its length. ``ssm`` ``[state-space layers, slots, H P / 128, N, 128]``
    float32 is the recurrent state, a tile 128 of a layer's ``H P`` channels on
    the lanes and the state's ``N`` on the sublanes (``ops/ssm.py::to_pool``: the
    layout the decode kernel reads and writes as it is), ``conv`` ``[state-space layers, slots, (d_conv - 1)
    x (H P + 2 G N)]`` the convolution's last inputs, one lane-dense row a slot
    (``ops/ssm.py``; with a dimension of 3 of its own the chip's compiler laid
    it out 3-minor, padded to 128 lanes, and copied it: 2.4 GB). State-space
    layer ``s`` (counted among its kind) owns row ``s``; a sequence owns slot
    ``i`` of every row from its first token to its flush (``ragged.
    StateManager``), and a program's ROW ``i`` is slot ``i``: a layer reads and
    writes the first ``rows`` slots of its row of the pool as ONE slice, in
    place, and never gathers or scatters by sequence. A slot is not cleared
    when it changes hands: a row fed from position 0 starts from zeros.

    A pattern of Gated DeltaNet layers (``linear_attention``) keeps the same
    two arrays: ``ssm`` ``[such layers, slots, Hv, Dk, Dv]`` float32, a value
    head's state one ``[Dk, Dv]`` tile with the values on the lanes
    (``ops/pallas/gdn_update.py``), ``conv`` the last inputs of ``[q | k | v]``."""

    ssm: jax.Array
    conv: jax.Array


_KV_QUANT_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
_LANES = 128


def latent_pool_width(cfg: TransformerConfig) -> int:
    """Columns of a latent pool's row: latent + rotary key, in whole lane tiles."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // _LANES) * _LANES


def index_pool_width(cfg: TransformerConfig) -> int:
    """Columns of an index pool's row: the indexer's one key a token, in whole lane tiles (0: no indexer)."""
    return -(-cfg.index_head_dim // _LANES) * _LANES if cfg.index_topk else 0


def by_head(cfg: TransformerConfig, kind: str = "attention") -> Tuple[int, int, int]:
    """(kv heads, a token's key columns, its value columns) in a page of the class that layers of ``kind`` write:
    ``kvH * hd`` of each for every model but a pattern with a sliding kind, whose two kinds state their own heads
    and widths (``transformer.sliding_kind``): a key of 192 columns a head beside a value of 128 lies in its page
    as it is, ``kvH * 192`` columns of ``k`` and ``kvH * 128`` of ``v``, nothing padded."""
    if cfg.sliding is None:
        return cfg.kv_heads, cfg.kv_heads * cfg.dims_per_head, cfg.kv_heads * cfg.dims_per_head
    own = sliding_kind(cfg, kind)
    return own["kv_heads"], own["kv_heads"] * own["head_dim"], own["kv_heads"] * own["v_head_dim"]


def init_pool(cfg: TransformerConfig, num_blocks: int, block_size: int, dtype: Any = jnp.bfloat16,
              kv_quant: Optional[str] = None) -> PagedKVPool:
    if cfg.eva_window and kv_quant is not None:
        raise ValueError(
            f"kv_quant={kv_quant!r} with EVA attention: a summary row is a weighted sum of "
            "keys and has no per-token scale; use a bf16/fp32 pool")
    if cfg.latent_attention:
        if kv_quant is not None:
            raise ValueError(
                f"kv_quant={kv_quant!r} with latent attention: a latent pool has no quantized "
                "form (one scale a token a layer is not carried); use a bf16/fp32 pool")
        pages = (cfg.num_layers * num_blocks, block_size)
        return PagedKVPool(k=jnp.zeros(pages + (latent_pool_width(cfg),), dtype),
                           v=jnp.zeros(pages + (index_pool_width(cfg),), dtype) if cfg.index_topk else None)
    # (of a layer pattern, the attention layers alone hold pages, at the geometry of their kind)
    heads, keys, values = by_head(cfg)
    pages = (cfg.attention_layers * num_blocks, block_size)
    if kv_quant is None:
        return PagedKVPool(k=jnp.zeros(pages + (keys,), dtype), v=jnp.zeros(pages + (values,), dtype))
    if kv_quant not in _KV_QUANT_DTYPES:
        raise ValueError(f"kv_quant must be None|'int8'|'fp8', got {kv_quant!r}")
    qdt = _KV_QUANT_DTYPES[kv_quant]
    sshape = (pages[0], block_size * heads)
    return PagedKVPool(k=jnp.zeros(pages + (keys,), qdt), v=jnp.zeros(pages + (values,), qdt),
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))


def init_ring_pool(cfg: TransformerConfig, ring_blocks: int, block_size: int, dtype: Any = jnp.bfloat16):
    """The sliding layers' class of page (``Pools.ring``): ``ring_blocks`` pages a layer, at the sliding kind's
    own geometry."""
    _, keys, values = by_head(cfg, "sliding_attention")
    pages = (cfg.sliding_layers * ring_blocks, block_size)
    return PagedKVPool(k=jnp.zeros(pages + (keys,), dtype), v=jnp.zeros(pages + (values,), dtype))


def _state_shapes(cfg: TransformerConfig):
    """(state layers, a slot's float32 state a layer, its conv tail's row) of a pattern's recurrent layers."""
    if cfg.gdn_layers:
        if cfg.ssm_layers:
            raise ValueError("a layer pattern with both 'mamba' and 'linear_attention' layers: one state pool "
                             "holds one kind of state")
        g = cfg.gdn
        return cfg.gdn_layers, (g.n_v_heads, g.head_k_dim, g.head_v_dim), (g.d_conv - 1) * g.conv_dim
    sizes = cfg.ssm
    tile = ssm.pool_tile(sizes.d_inner)
    return cfg.ssm_layers, (sizes.d_inner // tile, sizes.d_state, tile), (sizes.d_conv - 1) * sizes.conv_dim


def init_state_pool(cfg: TransformerConfig, slots: int, dtype: Any = jnp.bfloat16) -> StatePool:
    layers, state, conv = _state_shapes(cfg)
    return StatePool(ssm=jnp.zeros((layers, slots) + state, jnp.float32), conv=jnp.zeros((layers, slots, conv), dtype))


class Pools(NamedTuple):
    """What every step program is handed in the pool's place, donated, and hands back: ``kv`` the class of page
    every model has, ``state`` the state pool of a pattern's Mamba-2 or Gated DeltaNet layers, ``ring`` the pages
    of its ``sliding_attention`` layers (:class:`RingLayout`). A ``None`` field has no leaves: a model without
    state or a ring hands its programs the page pool's arrays and nothing else."""

    kv: PagedKVPool
    state: Optional[StatePool] = None
    ring: Optional[PagedKVPool] = None


# ------------------------------------------------------------ the plan
@dataclasses.dataclass(frozen=True)
class PageClass:
    """One class of page a row holds: ``name`` the field of :class:`Pools` its arrays are, ``layers`` that write
    it, and a token's row in it: ``width`` columns in ``k``, ``second`` in ``v`` (0: no ``v``), which holds what
    ``second_holds`` says: THE place that says a :class:`PagedKVPool`'s ``v`` is index keys under an indexer."""

    name: str
    layers: int
    heads: int  # kv heads a row is laid out by (0: one slab for all heads, nothing to split over tp)
    width: int
    second: int
    second_holds: str  # "values" | "index keys" | ""
    quantized: bool  # has the int8 / fp8 form: 1-byte values and a float32 scale a token a head

    def bytes_per_token(self, itemsize: int, kv_quant: Optional[str] = None) -> int:
        if self.heads and self.second == self.width:  # the formula the pre-flight guard and the capacity benchmark share
            return kv_slot_bytes(self.layers, self.heads, self.width // self.heads, itemsize, kv_quant)
        return self.layers * itemsize * (self.width + self.second)  # (a class of two widths has no quantized form)

    def page_bytes(self, block_size: int, itemsize: int) -> int:
        """One page of ONE layer: ``block_size`` slots of a token's key and value."""
        return block_size * itemsize * (self.width + self.second)


def attention_kind(cfg: TransformerConfig) -> str:
    """Which attention reads and writes a model's pages: the key of its builder (``paged._ATTENTION``)."""
    if cfg.eva_window:
        return "eva"
    if cfg.sliding is not None:
        return "windowed"
    if cfg.latent_attention:
        return "indexed" if cfg.index_topk else "latent"
    return "plain"


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """What serving keeps of a sequence of one model, from its config alone (:func:`cache_plan`): ``kind`` the
    attention that reads and writes the pages, ``classes`` the classes of page a row holds (the first is the one
    every model has, a second the ring's), ``layout`` the row's block table, ``state`` the kind of a pattern's
    recurrent layers (``None``, ``"mamba"``, ``"linear_attention"``): a slot a sequence."""

    config: TransformerConfig
    block_size: int
    kind: str
    classes: Tuple[PageClass, ...]
    layout: _Layout
    state: Optional[str]

    @property
    def max_pages(self) -> int:
        """Columns of a row's block table."""
        return self.layout.width

    @property
    def ring(self) -> Optional[PageClass]:
        return self.classes[1] if len(self.classes) > 1 else None

    @property
    def windows(self) -> Optional[WindowLayout]:
        """The row's layout where windows close into summaries (EVA), else None."""
        return self.layout if self.kind == "eva" else None

    @property
    def rings(self) -> Optional[RingLayout]:
        """The row's layout where a sliding kind keeps a ring of pages, else None."""
        return self.layout if self.ring is not None else None

    @property
    def ring_columns(self) -> int:
        """Ring pages a row holds at the most (0: no sliding kind)."""
        return self.layout.window_pages if self.ring is not None else 0

    # ---- bytes
    def bytes_per_token(self, kv_dtype: Any, kv_quant: Optional[str] = None) -> int:
        """What a cached token costs in the first class of page, every layer's."""
        return self.classes[0].bytes_per_token(jnp.dtype(kv_dtype).itemsize, kv_quant)

    def ring_bytes(self, ring_blocks: int, kv_dtype: Any) -> int:
        """The ring's class: ``ring_blocks`` pages a sliding layer, at a token's bytes a sliding layer."""
        if self.ring is None:
            return 0
        return ring_blocks * self.block_size * self.ring.bytes_per_token(jnp.dtype(kv_dtype).itemsize)

    def state_bytes(self, slots: int, dtype: Any) -> int:
        """A slot a sequence a state layer: the float32 state and the conv tail (0: no recurrent state)."""
        if self.state is None:
            return 0
        layers, state, conv = _state_shapes(self.config)
        return slots * layers * (int(np.prod(state)) * 4 + conv * jnp.dtype(dtype).itemsize)

    def workspace_bytes(self, config: Any, itemsize: int) -> int:
        """A step's attention temporaries, for the pre-flight guard (``config``: the engine's)."""
        cfg = self.config
        if self.kind == "eva":
            # the chunk program attends inside the chunk, a window at a time: its temporaries are a call's
            # tokens x (two fp32 residuals, q/k/v and the attention's fp32 merge, the GLU's pair), not a
            # gathered context
            tokens = config.max_ragged_batch_size or config.row_bucket * config.chunk_bucket
            return tokens * (2 * cfg.hidden_size * 4 + 4 * cfg.num_heads * cfg.dims_per_head * itemsize
                             + 2 * cfg.intermediate_size * itemsize)
        # the gather fallback's: one layer's gathered (dequantized) KV blocks + fp32 score/prob arrays for a
        # bucketed step (round-10 calibration: without it the serving estimate under-counted 2-3.5x on configs
        # whose pool doesn't dominate; the Pallas path needs less: estimates cover the worst dispatching path)
        return config.row_bucket * self.max_pages * self.block_size * (
            2 * cfg.kv_heads * cfg.dims_per_head * itemsize + 2 * cfg.num_heads * config.chunk_bucket * 4)

    # ---- what the kind does not serve with
    def refusals(self, config: Any, mesh: Any) -> List[str]:
        """What this cache does not serve with under the engine's ``config`` on ``mesh``: one message a kind that
        refuses, naming everything of it that is asked (they check outside input; tests match on their words)."""
        held = (self.kind, self.state and "state", self.kind == "indexed" and "latent")
        out = []
        for kind, rows_of in _REFUSES.items():  # (in the order the engine always raised them)
            if kind in held:
                lead, rows = rows_of(self.config, config, mesh.shape["tp"])
                if any(bad for bad, _ in rows):
                    out.append(lead + "; ".join(what for bad, what in rows if bad))
        return out

    def migration_refusal(self, importing: bool = False) -> Optional[str]:
        """Why a request's pages do not migrate: what the row holds beside position-ordered pages of one class,
        which is all the wire format carries (None: nothing, it migrates). An import checks the state alone."""
        if importing:
            return _NOT_MIGRATED["state_in"] if self.state else None
        held = "eva" if self.kind == "eva" else "state" if self.state else "ring" if self.ring is not None else None
        return _NOT_MIGRATED.get(held)

    # ---- the arrays
    def init(self, num_blocks: int, ring_blocks: int, slots: int, dtype: Any = jnp.bfloat16,
             kv_quant: Optional[str] = None, state_dtype: Any = None) -> Pools:
        """The pools, zeros: ``num_blocks`` pages a layer of the first class in ``dtype`` (or ``kv_quant``'s
        form), ``ring_blocks`` a sliding layer, ``slots`` state slots whose conv tails are ``state_dtype``."""
        cfg = self.config
        return Pools(
            kv=init_pool(cfg, num_blocks, self.block_size, dtype, kv_quant=kv_quant),
            state=None if self.state is None else init_state_pool(cfg, slots, state_dtype or dtype),
            ring=None if self.ring is None else init_ring_pool(cfg, ring_blocks, self.block_size, dtype))


def cache_plan(cfg: TransformerConfig, block_size: int, max_seq_len: int) -> CachePlan:
    """The plan of ``cfg``'s cache at pages of ``block_size`` tokens and rows of ``max_seq_len`` at the most."""
    kind = attention_kind(cfg)
    if kind in ("latent", "indexed"):  # one slab a token a layer, shared by all heads, and its one index key
        first = PageClass("kv", cfg.num_layers, 0, latent_pool_width(cfg), index_pool_width(cfg),
                          "index keys" if cfg.index_topk else "", quantized=False)
    else:  # (of a layer pattern, the attention layers alone hold these pages; EVA's rows have no per-token scale)
        first = PageClass("kv", cfg.attention_layers, *by_head(cfg), "values", quantized=kind == "plain")
    classes, layout = (first,), PlainLayout(block_size, max_seq_len)
    if kind == "eva":
        layout = WindowLayout(cfg.eva_window, block_size, max_seq_len)
    elif kind == "windowed":  # two classes of page, each at its kind's own geometry (heads, key and value widths)
        layout = RingLayout(cfg.sliding.window, block_size, max_seq_len)
        classes += (PageClass("ring", cfg.sliding_layers, *by_head(cfg, "sliding_attention"), "values",
                              quantized=False),)
    state = None if not cfg.state_layers else ("linear_attention" if cfg.gdn_layers else "mamba")
    return CachePlan(cfg, block_size, kind, classes, layout, state)


# ------------------------------------------------------------ what a kind does not serve with
# a kind -> (model config, engine config, tp) -> (the message's lead, [(asked, what it is)])
def _eva_refuses(cfg, config, tp):
    chunk, bs = cfg.eva_chunk, config.kv_block_size
    return "EVA attention (eva_window > 0) does not serve with ", [
        (chunk != bs, f"kv_block_size={bs}: a page of exact rows closes into ONE summary row, which needs "
         f"kv_block_size={chunk}, the model's chunk"),
        (cfg.eva_window % (chunk * chunk) != 0, f"eva_window={cfg.eva_window}: a closed window's summaries fill "
         f"whole pages (a multiple of {chunk} x {chunk})"),
        (config.spec_decode > 0, "spec_decode: drafts are a chunk of several tokens in the middle of a window, "
         "which the chunk path does not take"),
        (config.kv_quant is not None, f"kv_cache_dtype={config.kv_dtype_name!r}: a summary row has no per-token scale"),
        (config.prefix_cache, "prefix_cache: a page's content is not a function of a block of the prompt's tokens "
         "once windows close into summaries"),
        (tp > 1, f"tp={tp}: the pooling vectors and the closing are not partitioned over heads"),
        (config.chunk_bucket % bs != 0, f"chunk_bucket={config.chunk_bucket}: a chunk is whole pages of {bs}"),
    ]


def _windowed_refuses(cfg, config, tp):
    return "a sliding kind (sliding_attention layers) does not serve with ", [
        (config.prefix_cache, "prefix_cache: a sliding layer keeps a prompt's LAST window alone, so a shared "
         "prefix's pages hold no sliding layer's keys for the suffix to read"),
        (config.spec_decode > 0, "spec_decode: drafts are a chunk of several tokens past position 0, which would "
         "have to read the ring and the global pages (ROADMAP R3b)"),
        (config.kv_quant is not None, f"kv_cache_dtype={config.kv_dtype_name!r}: the ring pool has no scale pages, "
         "and a fresh prompt's bulk write quantizes nothing"),
        (tp > 1, f"tp={tp}: the two classes of page and the ring's roll are not partitioned over heads"),
        (config.chunk_bucket % config.kv_block_size != 0,
         f"chunk_bucket={config.chunk_bucket}: a fresh prompt writes whole pages of {config.kv_block_size}"),
    ]


def _state_refuses(cfg, config, tp):
    return "recurrent state (state-space layers) does not serve with ", [
        (config.prefix_cache, "prefix_cache: a prefix's pages without the recurrent state at its end are not a "
         "prefix, and no state is kept per block"),
        (config.spec_decode > 0, "spec_decode: a rejected draft has already moved the state, and nothing rolls "
         "it back"),
        (tp > 1, f"tp={tp}: the state pool and the mixer's projections are not partitioned over heads"),
    ]


def _latent_refuses(cfg, config, tp):
    keys = " and the index keys are kept in the cache's own type, not fp8 or int8" if cfg.index_topk else ""
    return "", [(config.kv_quant is not None, f"kv_cache_dtype={config.kv_dtype_name!r} with latent attention: the "
                 f"latent pool has no quantized form{keys}; use a bf16 or fp32 pool")]


def _indexed_refuses(cfg, config, tp):
    return "a sparse-attention indexer (index_topk > 0) does not serve with ", [
        (tp > 1, f"tp={tp}: the indexer's heads and the selection are not partitioned over heads"),
        (config.spec_decode > 0, "spec_decode: the one proposer is the n-gram lookup, and the model's "
         "multi-token-prediction layer, which would propose here, is not built"),
        (config.prefix_cache, "prefix_cache: a shared prefix's pages hold its index keys too, but no test has fed a "
         "suffix through an indexer yet"),
    ]


_REFUSES = {"eva": _eva_refuses, "windowed": _windowed_refuses, "state": _state_refuses,
            "latent": _latent_refuses, "indexed": _indexed_refuses}

_NOT_MIGRATED = {
    "eva": "KV-block migration of an EVA model: the wire format carries pages in position order and knows one kind "
           "of row; summary and window pages are not told apart",
    "state": "KV-block migration of a model with recurrent state: the wire format carries pages and knows no state "
             "slot; a request's state would stay behind",
    "ring": "KV-block migration of a model with a sliding kind: the wire format carries one class of page in "
            "position order; a row's ring pages, rolled by its position, would stay behind",
    "state_in": "KV-block migration into a model with recurrent state: the wire format carries pages and knows no "
                "state slot",
}
