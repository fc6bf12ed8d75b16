"""Compile the main path's kernels for the REAL chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a chip that
is described, not attached (``on-chip-measurement`` guide, section 2). The
interpret-mode tests cannot see what Mosaic refuses — block shapes off the
(8, 128) rule, DMA slices off the tiling, too much VMEM — and every kernel
here was refused at least once before ISSUE 22. Nothing runs: a compile that
passes is not a chip run (``chip_smoke.py`` is), and nothing here is a time.

Rules of this file (the guide's): the topology is described INSIDE a
module-scoped fixture that skips when it cannot be — never at import, in a
``skipif``, in ``parametrize`` or in ``conftest.py`` (only one process may
hold libtpu, and every xdist worker imports every test file); ``_interpret``
is steered by ``monkeypatch`` in the test, not by an option of the program;
the persistent compile cache is off around the compiles (an entry written
for a described chip cannot be read back without one). All in ONE file: a
second file could land on another worker, whose fixture would skip.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

GPT2 = dict(H=12, kvH=12, hd=64, E=768)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compiled_kernels(fn, *shapes) -> int:
    """Compile ``fn`` for the described chip; number of Mosaic kernels in it."""
    return jax.jit(fn).lower(*shapes).compile().as_text().count("tpu_custom_call")


def test_flash_attention_fwd_bwd_gpt2_width(one_chip, monkeypatch):
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((8, 1024, GPT2["H"], GPT2["hd"]), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_causal_attention(q, k, v).astype(jnp.float32).sum()

    assert _compiled_kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) >= 2


@pytest.mark.parametrize("shape,kernels", [
    ((2, 2048, 16, 64), 2),   # pythia-410m.train.seq2048's call
    ((1, 2048, 16, 128), 2),  # pythia-1.4b.train.zero3-4chip's, a chip
    ((1, 8192, 4, 128), 2),   # the longest whose dq slab the one pass keeps in VMEM
    ((1, 8192 + 512, 4, 128), 3),  # past it: the dq kernel and the dkv kernel
], ids=["cell-410m", "cell-1.4b", "longest-one-pass", "first-two-pass"])
def test_flash_backward_is_one_kernel_while_dq_fits_vmem(one_chip, monkeypatch, shape, kernels):
    """Forward and backward: two Mosaic kernels while a head's fp32 dq
    (double-buffered, beside the [512, 512] temporaries) fits the VMEM a
    kernel may scope, three past that. A VMEM refusal shows here."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_causal_attention(q, k, v).astype(jnp.float32).sum()

    assert _compiled_kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == kernels


@pytest.mark.parametrize("shape,kernels,Hkv", [
    ((2, 2048, 16, 64), 2, 16), ((1, 2048, 16, 128), 2, 4), ((1, 8192 + 512, 4, 128), 3, 4),
], ids=["cell-410m", "cell-1.4b-gqa", "first-two-pass"])
def test_flash_with_a_padding_mask_and_alibi_compiles(one_chip, monkeypatch, shape, kernels, Hkv):
    """The variants share the statistics' specs: with a padding mask (in the
    dkv kernel a key block's mask row becomes a column) and alibi slopes
    (``[H, 1, 8]``, a head a block: as ``[H, 8]`` Mosaic refused the block of
    one head, and the alibi path had never compiled for the chip)."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    B, S, H, D = shape
    sds = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731

    def loss(q, k, v, mask, slopes):
        return fa.flash_causal_attention(q, k, v, mask=mask, alibi_slopes=slopes).astype(jnp.float32).sum()

    assert _compiled_kernels(jax.grad(loss, argnums=(0, 1, 2)), sds(shape), sds((B, S, Hkv, D)),
                             sds((B, S, Hkv, D)), sds((B, S), jnp.int32), sds((H,), jnp.float32)) == kernels


@pytest.mark.parametrize("shape", [(2, 2048, 16, 64), (1, 2048, 16, 128)], ids=["cell-410m", "cell-1.4b"])
def test_the_train_scan_stacks_the_flash_statistics_unpadded(one_chip, monkeypatch, shape):
    """What the two train cells' layer scans keep of flash attention from a
    micro-step's forward to its backward, and what its backward hands back:
    the custom VJP's residual is the
    kernel's own lse, ``[B, H, 1, S]`` rows in ``T(1,128)`` tiles, so the 24
    layers' stack is its numbers and no more (as ``[B, H, S, 8]`` columns in
    ``T(8,128)`` tiles it was sixteen times them, 805 MB at the 410M cell's
    shape), and delta reaches the backward kernel as a row too."""
    import re

    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    layers, (B, S, H, D) = 24, shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((layers,), jnp.bfloat16, sharding=one_chip)

    def loss(x, w):
        def layer(h, wl):
            return h + fa.flash_causal_attention(h * wl, h, h), None

        return jax.lax.scan(layer, x, w)[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).compile().as_text()
    assert text.count("tpu_custom_call") == 2 and "flash_fwd" in text and "flash_bwd_dkv" in text
    assert not re.search(r"f32\[[\d,]*2048,8\]", text)
    # the backward kernel hands dq, dk and dv over in the model's dtype, scaled inside (no fp32 copy to HBM)
    assert re.search(r"%flash_bwd_dkv[\w.]* = \(bf16\[", text) and not re.search(r"%flash_bwd_dkv[\w.]* = \(f32", text)

    def stored(dims, order, sub, lanes):  # bytes of an f32 array as its layout's tile pads it
        dims, (minor, second) = [int(d) for d in dims.split(",")], [int(i) for i in order.split(",")[:2]]
        dims[minor] = -(-dims[minor] // int(lanes)) * int(lanes)
        dims[second] = -(-dims[second] // int(sub)) * int(sub)
        return 4 * int(np.prod(dims))

    stacked = {m.group(0): stored(*m.groups()) for m in re.finditer(
        r"f32\[(%d,[\d,]+)\]\{([\d,]+):T\((\d+),(\d+)\)" % layers, text)
        if np.prod([int(d) for d in m.group(1).split(",")]) == layers * B * H * S}
    assert stacked, "no stacked statistic found"
    assert max(stacked.values()) <= 2 * layers * B * H * S * 4, stacked


def _large_leaves(config):
    """Bytes of a layer's six large leaves in bf16, by the name their gathers and scatters carry."""
    E, I = config["hidden_size"], config["intermediate_size"]
    return {"attn/wq": 2 * E * E, "attn/wk": 2 * E * E, "attn/wv": 2 * E * E, "attn/wo": 2 * E * E,
            "mlp/w_up": 2 * E * I, "mlp/w_down": 2 * E * I}


def _leaf_of(op_name):
    return op_name.split("layers/")[-1].split("/shard_map")[0]


def _zero3_cell_compiled(monkeypatch, mesh_axes=None, layers=2):
    """(the tool, the cell's config, its ``train_step`` compiled for the described 2x2 at real widths and ``layers``
    layers): ``pythia-1.4b.train.zero3-4chip`` through ``tools/train_step_for_described_chip.py``, on the cell's own
    mesh or on ``mesh_axes`` with the same sixteen sequences a step."""
    import importlib.util
    import json
    import os
    import pkgutil

    import deepspeed_tpu.ops.pallas as pallas_pkg
    from benchmarks.lib import program
    from deepspeed_tpu.models import causal_lm_spec
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.topology import mesh as mesh_mod

    root = os.path.join(os.path.dirname(__file__), "..", "..", "..")
    spec = importlib.util.spec_from_file_location(
        "train_step_tool", os.path.join(root, "tools", "train_step_for_described_chip.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # the tool tells the program it is on the chip; put back what it sets when the test ends
    monkeypatch.setattr(registry, "_default_backend", registry._default_backend)
    monkeypatch.setattr(mesh_mod, "_ACTIVE_MESH", mesh_mod._ACTIVE_MESH)
    for info in pkgutil.iter_modules(pallas_pkg.__path__):
        module = __import__(f"deepspeed_tpu.ops.pallas.{info.name}", fromlist=["_interpret"])
        if hasattr(module, "_interpret"):
            monkeypatch.setattr(module, "_interpret", module._interpret)

    cell = "pythia-1.4b.train.zero3-4chip"
    workload = json.load(open(os.path.join(root, "benchmarks", "workloads", cell + ".json")))
    config = json.load(open(os.path.join(root, "benchmarks", "configs", workload["config"] + ".json")))
    config["num_hidden_layers"] = layers
    sequences, seq_len = int(workload["traffic"]["sequences"]), int(workload["traffic"]["seq_len"])
    engine = dict(workload["engine"])
    if mesh_axes is not None:
        replicas = mesh_axes.get("dp", 1) * mesh_axes.get("fsdp", 1)
        engine.update(mesh=mesh_axes, gradient_accumulation_steps=sequences // replicas)
    compiled = tool.compile_train_step(
        causal_lm_spec(program.model_config(config, jnp.bfloat16), example_seq_len=seq_len),
        engine, {"input_ids": np.zeros((sequences, seq_len), np.int32)})
    return tool, config, compiled


def test_the_zero3_layer_scan_states_its_own_collectives(topo, monkeypatch):
    """The ZeRO-3 cell's own ``train_step`` (``pythia-1.4b.train.zero3-4chip``,
    real widths, cut to two layers), compiled for the described 2x2 with
    ``tools/train_step_for_described_chip.py``: since PR 45 the products of
    the scanned layer gather their weights themselves (``runtime/zero.py``),
    so the scan's bodies hold the six large leaves as six whole all-gathers
    each (alone or inside an ``async_collective_fusion``), the backward body
    hands their gradients over under ``zero_scatter`` (a reduce-scatter, or
    the hops of a ring the program wrote itself: 75.5 MB a layer, what a
    reduce-scatter sends), and the partitioner's rings of
    ``collective-permute`` (75.5 MB in the forward body, 192.9 MB in the
    backward body at the parent) are gone. The gathered weight is never a
    residual of the scan, and a shard dimension that is not the leaf's own
    would show as an all-to-all of hundreds of MB. ``temp_gb`` at this cut was
    1.446 at PR 44's tree (my compile for the described chip, PR 45)."""
    import re

    layers = 2
    tool, config, compiled = _zero3_cell_compiled(monkeypatch, layers=layers)
    text = compiled.as_text()
    found = tool.census(text)
    forward, backward = (found[tool.name_of(tool.holding(text, kernel))] for kernel in ("flash_fwd", "flash_bwd_dkv"))

    E, I = config["hidden_size"], config["intermediate_size"]
    leaves, leaf_of = _large_leaves(config), _leaf_of
    for body in (forward, backward):
        gathers = sorted((leaf_of(op), round(mb * 1e6)) for kind, mb, op in body
                         if kind == "all-gather" and "zero_gather" in op)
        assert gathers == sorted(leaves.items())
        # no ring of the partitioner's: what permutes it leaves are an overhang (2.4 MB at most)
        assert sum(mb for kind, mb, op in body if kind == "collective-permute" and "zero_scatter" not in op) < 0.05 * 75.5
    assert not any("zero_scatter" in op or kind == "reduce-scatter" for kind, _, op in forward)
    # every leaf's gradient leaves under ``zero_scatter``, as a reduce-scatter or as hops the program wrote itself;
    # a chip sends three quarters of a leaf and no more (a whole leaf's all-reduce would send twice that)
    leaving = [(leaf_of(op), kind, mb) for kind, mb, op in backward if "zero_scatter" in op]
    assert {leaf for leaf, _, _ in leaving} == set(leaves)
    assert {kind for _, kind, _ in leaving} <= {"reduce-scatter", "collective-permute"}
    hops = sum(mb for _, kind, mb in leaving if kind == "collective-permute")
    assert hops <= 0.75 * sum(leaves.values()) / 1e6 + 0.1, hops
    assert all(mb < 1 for kind, mb, _ in backward if kind == "all-reduce")

    # no residual of the scan is a gathered weight: nothing stacks a whole leaf over the layers
    whole = [(E, I), (I, E), (E, config["num_attention_heads"], E // config["num_attention_heads"])]
    whole += [shape[1:] + shape[:1] for shape in whole]
    for shape in whole:
        assert not re.search(r"bf16\[%d,%s\]" % (layers, ",".join(str(d) for d in shape)), text), shape
    assert compiled.memory_analysis().temp_size_in_bytes / 1e9 <= 1.446 + 0.3
    largest = max(mb for body in found.values() for kind, mb, _ in body if kind == "all-to-all")
    assert largest < 20, largest


@pytest.mark.parametrize("mesh_axes", [{"dp": 2, "fsdp": 2}, {"fsdp": 2, "tp": 2}], ids=["dp2-fsdp2", "fsdp2-tp2"])
def test_the_zero3_cell_s_other_meshes_compile_for_the_chip(topo, monkeypatch, mesh_axes):
    """The two other ways to lay the ZeRO-3 cell over four chips, which ran on the CPU's devices only (PR 45's review,
    finding 4): both compile for the described 2x2 at the cell's widths, hold the flash kernels and fit a chip. With
    two replicas of two shards the scanned layer still gathers its own weights, half a leaf a chip, and hands the
    gradients over under ``zero_scatter``; with ``tp`` = 2 every collective of the scan stays the partitioner's
    (``runtime/zero.py::scan_gathers``) and none carries a name of ``runtime/zero.py``."""
    tool, config, compiled = _zero3_cell_compiled(monkeypatch, mesh_axes)
    text = compiled.as_text()
    found = tool.census(text)
    forward, backward = (found[tool.name_of(tool.holding(text, kernel))] for kernel in ("flash_fwd", "flash_bwd_dkv"))
    held = compiled.memory_analysis()
    assert (held.temp_size_in_bytes + held.argument_size_in_bytes) / 1e9 < 16
    if "tp" in mesh_axes:
        assert "zero_gather" not in text and "zero_scatter" not in text
        return
    leaves, leaf_of = _large_leaves(config), _leaf_of
    for body in (forward, backward):
        gathers = sorted((leaf_of(op), round(mb * 1e6)) for kind, mb, op in body
                         if kind == "all-gather" and "zero_gather" in op)
        assert gathers == sorted(leaves.items())
    leaving = {leaf_of(op) for kind, _, op in backward if "zero_scatter" in op}
    assert leaving == set(leaves) and not any("zero_scatter" in op for _, _, op in forward)


def test_flash_attention_partitions_over_four_chips(topo, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel; ``ops.causal_attention`` runs
    it per shard. This is the program the fsdp=4 train step traces."""
    from deepspeed_tpu.ops import causal_attention
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.topology.mesh import build_mesh, set_mesh

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    mesh = build_mesh(devices=topo.devices, axis_sizes={"fsdp": 4})
    set_mesh(mesh)
    x = jax.ShapeDtypeStruct((8, 1024, GPT2["H"], GPT2["hd"]), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(("dp", "fsdp"))))

    def loss(q, k, v):
        return causal_attention(q, k, v).astype(jnp.float32).sum()

    assert _compiled_kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) >= 2
    # the forward told its rows' live lengths: they split as the batch does, two rows a chip (PR 58)
    lengths = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=NamedSharding(mesh, P(("dp", "fsdp"))))
    text = jax.jit(lambda q, k, v, n: causal_attention(q, k, v, lengths=n)).lower(x, x, x, lengths).compile().as_text()
    (kernel,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert "flash_fwd" in kernel and "bf16[2,12,1024,64]" in kernel and "s32[4]{0}" in kernel  # (2 rows' blocks, tokens)


def _paged_shapes(sh, N, C, kv_quant, H, kvH, hd, pages=64, bs=16, num_blocks=512, layers=12):
    """The kernel's operands as ``inference/paged.py`` hands them over: the
    whole pool, every layer's pages, page-major in one rank-3 array."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sh)
    pool = sds((layers * num_blocks, bs, kvH * hd), jnp.int8 if kv_quant else jnp.bfloat16)
    shapes = [sds((N, C, H, hd), jnp.bfloat16), pool, pool, sds((N, pages), jnp.int32),
              sds((N, C), jnp.int32), sds((N,), jnp.int32)]
    if kv_quant:
        shapes += [sds((layers * num_blocks, bs * kvH), jnp.float32)] * 2
    return shapes, bs


@pytest.mark.parametrize("N,C,kv_quant,H,kvH,hd,pages", [
    (8, 1, False, 12, 12, 64, 64),    # GPT-2 decode, bf16 pool
    (8, 1, True, 12, 12, 64, 64),     # GPT-2 decode, int8 pool
    (8, 128, False, 12, 12, 64, 64),  # GPT-2 chunked prefill
    (8, 1, True, 32, 8, 128, 64),     # GQA at hd=128, int8 pool
    # pythia-1.4b.serve.batch's own shapes (ISSUE 30): the decode chain's call
    # under the cell's table of 2048 / 16 pages, and its one prefill program,
    # whose query block leaves the K/V slots the least VMEM
    (64, 1, False, 16, 16, 128, 128),
    (64, 256, False, 16, 16, 128, 128),
    (8, 5, False, 16, 16, 128, 128),  # a token and four drafts
], ids=["decode-bf16", "decode-int8", "chunk128-bf16", "gqa-hd128-int8",
        "cell-decode-64x1", "cell-prefill-64x256", "drafts-k4"])
def test_paged_attention_compiles(one_chip, monkeypatch, N, C, kv_quant, H, kvH, hd, pages):
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_interpret", lambda: False)
    shapes, bs = _paged_shapes(one_chip, N, C, kv_quant, H, kvH, hd, pages=pages)

    def fn(q, pk, pv, bt, qpos, lens, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return pa.flash_decode_paged(q, pk, pv, bt, qpos, bs, new_lens=lens, **kw)

    assert _compiled_kernels(fn, *shapes) == 1


def _latent_kernel_compiled(one_chip, monkeypatch, N, C, H, pages, cols, scale, mask_columns=0):
    """``flash_decode_latent`` compiled for the described chip at a cell's
    shapes, under a mask ``[N, C, mask_columns]`` where that is given: the text
    of its one Mosaic kernel's instruction, after the checks that it is ONE
    and that nothing of the pool's whole shape is a copy."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_interpret", lambda: False)
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    mask = [jax.ShapeDtypeStruct((N, C, mask_columns), jnp.bool_, sharding=one_chip)] * (mask_columns > 0)

    def fn(q, pool, bt, qpos, lens, mask=None):
        return pa.flash_decode_latent(q, pool, bt, qpos, 16, scale, 512, new_lens=lens, mask=mask)

    text = jax.jit(fn).lower(bf16(N, C, H, 640), bf16(pages, 16, 640), i32(N, cols), i32(N, C),
                             i32(N), *mask).compile().as_text()
    (kernel,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert not [line for line in text.splitlines()
                if re.search(r"= bf16\[%d,16,640\]\S* (copy|copy-start|transpose)\(" % pages, line)]
    return kernel


@pytest.mark.parametrize("N,C,steps,rows", [(64, 1, 64, 32), (64, 256, 512, 640), (8, 5, 8, 112)],
                         ids=["cell-decode-64x1", "cell-prefill-64x256", "drafts-k4"])
def test_latent_paged_attention_compiles(one_chip, monkeypatch, N, C, steps, rows):
    """``mla_paged_attn`` at glm-4.7-flash.serve.batch's own shapes: 20 heads
    against one 640-column slab a token (512 latent + 64 rotary + 64 of lane
    padding), values its first 512 columns, a table of 2048 / 16 pages; the
    256-token chunk goes in query tiles of 32 tokens x 20 heads (16 before
    PR 52: 1,024 grid steps of 320 rows), which is what the instruction's
    shape says, the most by doubling that fits the 16 MiB Mosaic scopes by
    default (the kernel asks for no more: what it took past that, the compiler
    would take from the arrays it keeps in fast memory for the whole program);
    one token and a token with its four drafts are one tile."""
    kernel = _latent_kernel_compiled(one_chip, monkeypatch, N, C, 20, 8 * 1024, 128, 1 / 16)
    assert re.search(r"%%mla_paged_attn\S* = bf16\[%d,%d,512\]" % (steps, rows), kernel), kernel


@pytest.mark.parametrize("N,C,steps,rows", [(64, 1, 64, 32), (8, 2048, 512, 1024), (8, 5, 8, 160)],
                         ids=["cell-decode-64x1", "cell-prefill-8x2048", "drafts-k4"])
def test_latent_paged_attention_compiles_at_32_heads(one_chip, monkeypatch, N, C, steps, rows):
    """``mla_paged_attn`` at xing4.0-29b-a4b.serve.long-prompt-batch's shapes:
    32 heads, a table of 4096 / 16 pages, a 1.5 GiB pool of 7 layers, a whole
    2,048-token prompt a row in query tiles of 32 tokens (1,024 rows; 16 tokens,
    512 rows and 1,024 grid steps before PR 52) against chunks of 32 pages."""
    kernel = _latent_kernel_compiled(one_chip, monkeypatch, N, C, 32, 7 * 11234, 256, 192 ** -0.5 * 2.00474)
    assert re.search(r"%%mla_paged_attn\S* = bf16\[%d,%d,512\]" % (steps, rows), kernel), kernel


@pytest.mark.parametrize("tq,ppcb,fits", [(32, 32, True), (24, 64, True), (36, 8, True),
                                          (36, 16, False), (40, 8, False), (32, 64, False)],
                         ids=lambda v: str(v))
def test_the_latent_form_s_count_of_vmem_is_the_compiler_s_verdict(one_chip, monkeypatch, tq, ppcb, fits):
    """``_latent_vmem_bytes`` against ``_LATENT_VMEM_BUDGET`` says what the
    chip's compiler says of a tile and a chunk at the xing prefill's shape, on
    both sides of the edge: the form ``_latent_form`` picks there, (32, 32),
    stands AT it, so a count that drifts from the compiler's shows here."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    assert (pa._latent_vmem_bytes(tq * 32, ppcb * 16, 640, 512, 2) <= pa._LATENT_VMEM_BUDGET) is fits
    monkeypatch.setattr(pa, "_latent_form", lambda *shapes: (tq, ppcb))
    if fits:
        _latent_kernel_compiled(one_chip, monkeypatch, 8, 2048, 32, 7 * 11234, 256, 0.1)
    else:
        with pytest.raises(Exception, match="vmem"):
            _latent_kernel_compiled(one_chip, monkeypatch, 8, 2048, 32, 7 * 11234, 256, 0.1)


@pytest.mark.parametrize("kernels", ["forward", "gradient"])
@pytest.mark.parametrize("m,K,N,E,dtype", [
    (65536, 2048, 1536, 64, jnp.bfloat16),  # glm-4.7-flash.serve.batch's (64, 256) prefill: gate and up
    (65536, 1536, 2048, 64, jnp.bfloat16),  # and the down-projection
    (65536, 3584, 1024, 64, jnp.bfloat16),  # xing4.0-29b-a4b's (8, 2048) prefill: gate and up
    (65536, 1024, 3584, 64, jnp.bfloat16),  # and its down-projection
    (512, 2048, 1536, 64, jnp.bfloat16),    # the smallest grouped call at 64 experts (T = 2E tokens x 4 picks)
    (32768, 4096, 14336, 8, jnp.bfloat16),  # mixtral-shaped: 8 experts, groups of thousands
    (32768, 14336, 4096, 8, jnp.bfloat16),  # and its down-projection, whose K does not fit whole: a k loop
    (40, 128, 256, 4, jnp.bfloat16),        # fewer rows than a tile: one tile of the rows, padded
    (65536, 2048, 1536, 64, jnp.float32),   # fp32 operands: blocks twice the bytes, sublanes of 8
    (81920, 2048, 512, 64, jnp.bfloat16),   # qwen3-next-80b-a3b: a group of its (128, 256) prefill's pairs over 64 HELD experts
    (81920, 512, 2048, 64, jnp.bfloat16),   # and the down-projection
], ids=["cell-gate-up", "cell-down", "xing-gate-up", "xing-down", "m512-e64", "mixtral-up", "mixtral-down", "m40", "cell-gate-up-fp32",
        "share-prefill-gate-up", "share-prefill-down"])
def test_grouped_matmul_compiles_at_the_chosen_tiles(one_chip, m, K, N, E, dtype, kernels):
    """The routed prefill's grouped matmul at the tiles ``_gmm_tiles`` picks
    from the call's shapes: Mosaic's VMEM refusal (16 MiB scoped, no limit of
    the call's own) is seen here, before a chip call is spent. And its
    gradient, which ``DropFreeMoE`` takes on a TPU: ``grad @ rhs.T`` through
    the same kernel and ``tgmm`` for the weights, each at tiles from its own
    shapes (the library's vjp at the forward's tiles was refused at
    mixtral-up: 18.25 MiB); the forward's product is not asked for there, so
    two kernels."""
    from deepspeed_tpu.inference.model import _gmm_padded

    lhs = jax.ShapeDtypeStruct((m, K), dtype, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((E, K, N), dtype, sharding=one_chip)
    gs = jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip)
    if kernels == "forward":
        assert _compiled_kernels(_gmm_padded, lhs, rhs, gs) == 1
    else:
        grad = jax.grad(lambda a, b, g: _gmm_padded(a, b, g).astype(jnp.float32).sum(), argnums=(0, 1))
        assert _compiled_kernels(grad, lhs, rhs, gs) == 2


@pytest.mark.parametrize("name", ["prefill_4x8192", "chain_24"])
def test_evabyte_programs_compile_at_the_cell_s_shapes(one_chip, monkeypatch, name):
    """``evabyte.serve.long-batch``'s two programs whole, for the described
    v5e at the cell's own shapes (8 layers at the published widths, a 7.5 GiB
    pool of 3,840 pages, a table of 40 summary + 128 window columns): the
    ``(4, 8192)`` prefill (the flash kernel a window, the summaries' part, the
    one-token rows' paged kernel, the closing under its ``cond``) and the
    chain of 8 steps at 24 rows (the paged kernel over [summary pages | window
    pages], the closing under its ``cond``). Each fits the chip beside the
    weights and the pool, returns the donated pool aliased, and copies,
    slices or re-lays neither the pool nor a layer of it: a second ``cond``
    that carries the pool in the layer, a ``switch`` of three branches and a
    ``lax.map`` nested in the layer scan each made the compiler copy it
    (PR 35); one ``cond`` whose branch loops over the closing rows does not."""
    import dataclasses
    import re

    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import flash_attention as fa, norms, paged_attention as pa

    for module in (pa, fa, norms):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    cfg = dataclasses.replace(config_from_hf(dict(
        model_type="evabyte", attention_class="eva", vocab_size=320, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=8, num_attention_heads=32, num_key_value_heads=32, max_position_embeddings=32768,
        window_size=2048, chunk_size=16, num_pred_heads=8, rms_norm_eps=1e-5, rope_theta=100000,
        norm_add_unit_offset=True, fp32_skip_add=True)), dtype=jnp.bfloat16)
    NB, bs, table = 3840, 16, 5 * 8 + 128
    bf16 = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(bf16, jax.eval_shape(
        lambda key: CausalLM(cfg).init({"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                                       train=False)["params"], jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(bf16, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16))))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if name == "chain_24":
        rows, limit_gb, kernels = 24, 2.6, ("paged_attn",)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program(params, pool, tokens, start_pos, tables, active, budgets, rng):
            return paged.ragged_decode_chain(params, cfg, pool, tokens, start_pos, tables, bs,
                                             active, budgets, rng, 8, None)

        args = (i32(rows), i32(rows), i32(rows, table), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
                i32(rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    else:
        rows, limit_gb, kernels = 4, 4.2, ("paged_attn", "flash_fwd")

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program(params, pool, tokens, positions, new_lens, tables):
            return paged.ragged_forward(params, cfg, pool, tokens, positions, new_lens, tables, bs)

        args = (i32(rows, 8192), i32(rows, 8192), i32(rows), i32(rows, table))
    compiled = program.lower(params, pool, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    assert pool_bytes == 24 * 160 * 16 * 8 * 16384  # 7.5 GiB: 24 rows of at most 160 pages
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < limit_gb * 1e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    for kernel in kernels:
        assert any("tpu_custom_call" in line and kernel in line for line in text.splitlines()), kernel
    assert "eva_close" in text and "conditional(" in text  # the closing, taken only when a row closes
    layer = r"bf16\[(%d|%d),%d,%d\]" % (cfg.num_layers * NB, NB, bs, cfg.kv_heads * cfg.dims_per_head)
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|dynamic-slice|reshape|transpose)\(" % layer, line)]
    assert not moved, moved


@pytest.mark.parametrize("name", ["prefill_64x256", "chain_64"])
def test_granite_programs_compile_at_the_cell_s_shapes(one_chip, monkeypatch, name):
    """``granite-4.0-h-micro.serve.long-output-batch``'s two programs whole, for
    the described v5e at the cell's own shapes (all 40 layers at the published
    widths, 64 state slots = 4.89 GB of recurrent state, a 0.5 GiB page pool of
    the four attention layers): the ``(64, 256)`` prefill (the chunked scan a
    state-space layer, the flash or paged kernel an attention layer) and the
    chain of 8 steps at 64 rows (the one-token recurrence, ``ssm_update``, nine
    calls a period). Each fits the chip
    beside the weights and both pools, returns BOTH donated pools aliased, and
    holds no instruction of the state pool's whole shape that is a copy: the
    state is updated in place, a row of the pool a layer."""
    import dataclasses
    import json
    import re

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import (conv_update, flash_attention as fa, norms, paged_attention as pa,
                                          ssm_update)

    for module in (pa, fa, norms, ssm_update, conv_update):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config("granite-4.0-h-micro"))),
                              dtype=jnp.bfloat16)
    engine = harness.load_workload("granite-4.0-h-micro.serve.long-output-batch")["engine"]
    bs, rows = engine["kv_block_size"], engine["max_seqs"]
    NB, table = engine["kv_pool_bytes"] // (bs * 8192), engine["max_seq_len"] // bs
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: CausalLM(cfg).init({"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                                       train=False)["params"], jax.random.PRNGKey(0)))
    pools = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16), cache.init_state_pool(cfg, rows, jnp.bfloat16))))
    assert pools.kv.k.shape == (4 * 4096, 16, 512) and pools.state.ssm.shape == (36, 64, 32, 128, 128)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if name == "chain_64":
        limit_gb = 1.5

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pools, tokens, start_pos, tables, active, budgets, rng):
            return paged.ragged_decode_chain(params, cfg, pools, tokens, start_pos, tables, bs,
                                             active, budgets, rng, engine["decode_chain"], None)

        args = (i32(rows), i32(rows), i32(rows, table), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
                i32(rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    else:
        limit_gb = 3.0

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pools, tokens, positions, new_lens, tables):
            return paged.ragged_forward(params, cfg, pools, tokens, positions, new_lens, tables, bs)

        chunk = engine["chunk_bucket"]
        args = (i32(rows, chunk), i32(rows, chunk), i32(rows), i32(rows, table))
    compiled = program_.lower(params, pools, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pools))
    assert pool_bytes == 64 * 36 * (2097152 + 26112) + 2 ** 29
    assert mem.alias_size_in_bytes >= pool_bytes
    print(json.dumps({"program": name, "temp_gb": mem.temp_size_in_bytes / 1e9,
                      "argument_gb": mem.argument_size_in_bytes / 1e9}))
    assert mem.temp_size_in_bytes < limit_gb * 1e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    kernels = ("paged_attn", "ssm_update", "conv_update") if name == "chain_64" else ("paged_attn",)
    for kernel in kernels:  # the one-token recurrence is a kernel of its own name, the pool aliased through it
        assert any("tpu_custom_call" in line and kernel in line for line in text.splitlines()), kernel
    # a decode step's convolution is one kernel, in place on the conv pool: the chain holds NO instruction of a
    # layer's row of it, sliced out or as [rows, K - 1, X]; a prompt keeps conv_inputs' lines
    assert pools.state.conv.shape == (36, 64, 3 * 4352)
    if name == "chain_64":
        rows_of_the_tail = [line.strip()[:200] for line in text.splitlines()
                            if re.search(r"= bf16\[(1,64,13056|64,13056|64,3,4352)\]", line)]
        assert not rows_of_the_tail, rows_of_the_tail
        # nor is the pool itself moved: handed to a kernel whole, its 60 MB fit the chip's fast memory, and the
        # compiler copied it there and back around every period (480 MB a step) until the kernel pinned it in HBM
        moved = [line.strip()[:200] for line in text.splitlines()
                 if re.search(r"= \(?bf16\[36,64,13056\]\S* (copy|copy-start|transpose)\(|bf16\[36,64,13056\]\S*S\(1\)", line)]
        assert not moved, moved
    else:
        assert "conv_update" not in text
    # nothing copies or re-lays the state pool, a layer's row of it, or the rows of a row
    state = r"f32\[(36,64|1,64|64),32,128,128\]"
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose)\(" % state, line)]
    assert not moved, moved


@pytest.mark.parametrize("name", ["prefill_128x256", "chain_128"])
def test_qwen3_next_programs_compile_at_the_cell_s_shapes(one_chip, monkeypatch, name):
    """``qwen3-next-80b-a3b.serve.long-output-wave128``'s two programs whole, for
    the described v5e at the cell's own shapes (12 layers at the published
    widths, 64 of 512 experts held, 128 state slots = 2.47 GB of delta-rule
    state, a 0.625 GiB page pool of the three attention layers), with the picks
    handed out as the timed path hands them: the ``(128, 256)`` prefill (the
    chunked delta rule a DeltaNet layer, the paged kernel's query block at 16 x
    256 over 2 KV heads, the share's sorted dispatch through megablox ``gmm``,
    which the chip takes and this host's ``lax.ragged_dot`` stands in for
    unless asked) and the chain of 8 steps at 128 rows (``gdn_update``, three
    calls a period; a block table 64 pages wide; the held experts some row
    picked by the kernel ``moe_decode``, four calls a period, on the WHOLE
    stacked weights, which the scan closes over: a custom call on the scan's
    slice of them, ``gmm`` or this one, would have the slice COPIED for it, 7.2
    GB a step, PERF.md PR 48 and PR 50). Each fits the chip beside the
    weights and both pools, returns BOTH donated pools aliased, and holds no
    instruction of the state pool's whole shape that is a copy."""
    import dataclasses
    import json
    import re

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, model, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import (conv_update, flash_attention as fa, gdn_update, moe_decode, norms,
                                          paged_attention as pa)

    for module in (pa, fa, norms, gdn_update, conv_update, moe_decode):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    monkeypatch.setattr(model, "_grouped_matmul", lambda lhs, rhs, sizes: model._gmm_padded(lhs, rhs, sizes))
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config("qwen3-next-80b-a3b"))),
                              dtype=jnp.bfloat16)
    engine = harness.load_workload("qwen3-next-80b-a3b.serve.long-output-wave128")["engine"]
    bs, rows = engine["kv_block_size"], engine["max_seqs"]
    NB, table = engine["kv_pool_bytes"] // (bs * 6144), engine["max_seq_len"] // bs
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: CausalLM(cfg).init({"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                                       train=False)["params"], jax.random.PRNGKey(0)))
    pools = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16), cache.init_state_pool(cfg, rows, jnp.bfloat16))))
    assert pools.kv.k.shape == (3 * 6826, 16, 512) and pools.state.ssm.shape == (9, 128, 32, 128, 128)
    assert pools.state.conv.shape == (9, 128, 3 * 8192)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if name == "chain_128":
        limit_gb = 0.3  # (0.11; 1.78 with the product over all 64 held experts, 0.50 with the sorted dispatch)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pools, tokens, start_pos, tables, active, budgets, rng):
            return paged.ragged_decode_chain(params, cfg, pools, tokens, start_pos, tables, bs,
                                             active, budgets, rng, engine["decode_chain"], None, with_picks=True)

        args = (i32(rows), i32(rows), i32(rows, table), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
                i32(rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    else:
        limit_gb = 5.5

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pools, tokens, positions, new_lens, tables):
            return paged.ragged_forward(params, cfg, pools, tokens, positions, new_lens, tables, bs, with_picks=True)

        chunk = engine["chunk_bucket"]
        args = (i32(rows, chunk), i32(rows, chunk), i32(rows), i32(rows, table))
    compiled = program_.lower(params, pools, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pools))
    assert pool_bytes == 128 * 9 * (2097152 + 49152) + 3 * 6826 * 16 * 512 * 2 * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    print(json.dumps({"program": name, "temp_gb": mem.temp_size_in_bytes / 1e9,
                      "argument_gb": mem.argument_size_in_bytes / 1e9}))
    assert mem.temp_size_in_bytes < limit_gb * 1e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    # (a decode step's 128 rows go through every held expert some row picked, read from the stack: no gmm there)
    kernels = ({"paged_attn": 1, "gdn_update": 3, "conv_update": 3, "moe_decode": 4} if name == "chain_128"
               else {"paged_attn": 1, "gmm": 12})
    # (by the custom calls: the table of source files may name megablox's through a cached jax.numpy function)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert not any(("gmm" if name == "chain_128" else "moe_decode") in line for line in calls)
    if name == "chain_128":
        assert not _experts_moved(text, 3, 64, 2048, 512)
    # a decode step's convolution is one kernel, in place on the conv pool: the chain holds NO instruction of a
    # layer's row of it, sliced out or as [rows, K - 1, X] in either dtype; a prompt keeps conv_inputs' lines
    if name == "chain_128":
        rows_of_the_tail = [line.strip()[:200] for line in text.splitlines()
                            if re.search(r"= (bf16|f32)\[(1,128,24576|128,24576|128,3,8192)\]", line)]
        assert not rows_of_the_tail, rows_of_the_tail
        # nor is the pool itself moved into the chip's fast memory and back (57 MB: it would fit)
        moved = [line.strip()[:200] for line in text.splitlines()
                 if re.search(r"= \(?bf16\[9,128,24576\]\S* (copy|copy-start|transpose)\(|bf16\[9,128,24576\]\S*S\(1\)", line)]
        assert not moved, moved
    else:
        assert "conv_update" not in text
    for kernel, least in kernels.items():  # the one-token rule is a kernel of its own name, the pool aliased through it
        assert sum(kernel in line for line in calls) >= least, kernel
    # nothing copies or re-lays the state pool, a layer's row of it, or the rows of a row
    state = r"f32\[(9,128|1,128|128),32,128,128\]"
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose)\(" % state, line)]
    assert not moved, moved
    # nor does the compiler, short of room, make the pool's update twice (it did with 64 rows a group of the
    # chunked rule: ``bitcast_dynamic-update-slice_fusion.21.remat``, a second pool)
    assert not re.search(r"dynamic-update-slice\S*\.remat\S* = f32\[9,128,32,128,128\]", text)


@pytest.mark.parametrize("name", ["prefill_64x256", "chain_64"])
def test_granite_routed_programs_compile_at_the_cell_s_shapes(one_chip, monkeypatch, name):
    """``granite-4.0-h-small.serve.long-output-wave64``'s two programs whole, for
    the described v5e at the cell's own shapes (one period of ten layers at the
    published widths, 36 of 72 experts held in every layer, 64 state slots =
    2.445 GB of recurrent state at 64 tiles a row, a 0.25 GiB page pool of the
    one attention layer), with the picks handed out as the timed path hands
    them: the ``(64, 256)`` prefill (the chunked scan at ``d_inner`` 8,192, the
    share's sorted dispatch through megablox ``gmm`` in two groups of 81,920
    pairs) and the chain of 8 steps at 64 rows (``ssm_update`` and
    ``conv_update`` nine calls a period, ``moe_decode`` ten, on the stacked
    experts WHOLE). Each fits the chip under 15.0 GiB beside the weights and
    both pools (ISSUE 51's condition for the wave of 64), returns BOTH donated
    pools aliased, and holds no instruction of a layer's experts' shape nor of
    either pool's whole shape in the chip's fast memory (memory space 1)."""
    import dataclasses
    import json
    import re

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, model, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import (conv_update, flash_attention as fa, moe_decode, norms,
                                          paged_attention as pa, ssm_update)

    for module in (pa, fa, norms, ssm_update, conv_update, moe_decode):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    monkeypatch.setattr(model, "_grouped_matmul", lambda lhs, rhs, sizes: model._gmm_padded(lhs, rhs, sizes))
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config("granite-4.0-h-small"))),
                              dtype=jnp.bfloat16)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert, cfg.moe_top_k) == (36, 72, 0, 10)
    engine = harness.load_workload("granite-4.0-h-small.serve.long-output-wave64")["engine"]
    bs, rows = engine["kv_block_size"], engine["max_seqs"]
    NB, table = engine["kv_pool_bytes"] // (bs * 4096), engine["max_seq_len"] // bs
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    # (the configuration names no dtype, so the initialisers draw in float32; the harness rounds every leaf to bf16)
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), CausalLM(cfg).init(
            {"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]),
        jax.random.PRNGKey(0)))
    pools = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16), cache.init_state_pool(cfg, rows, jnp.bfloat16))))
    assert pools.kv.k.shape == (4096, 16, 1024) and pools.state.ssm.shape == (9, 64, 64, 128, 128)
    assert pools.state.conv.shape == (9, 64, 3 * 8448)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if name == "chain_64":
        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pools, tokens, start_pos, tables, active, budgets, rng):
            return paged.ragged_decode_chain(params, cfg, pools, tokens, start_pos, tables, bs,
                                             active, budgets, rng, engine["decode_chain"], None, with_picks=True)

        args = (i32(rows), i32(rows), i32(rows, table), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
                i32(rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    else:
        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pools, tokens, positions, new_lens, tables):
            return paged.ragged_forward(params, cfg, pools, tokens, positions, new_lens, tables, bs, with_picks=True)

        chunk = engine["chunk_bucket"]
        args = (i32(rows, chunk), i32(rows, chunk), i32(rows), i32(rows, table))
    compiled = program_.lower(params, pools, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pools))
    assert pool_bytes == 64 * 9 * (4194304 + 50688) + 2 ** 28
    assert mem.alias_size_in_bytes >= pool_bytes
    print(json.dumps({"program": name, "temp_gb": mem.temp_size_in_bytes / 1e9,
                      "argument_gb": mem.argument_size_in_bytes / 1e9,
                      "peak_gib": (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30}))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0 * 2 ** 30  # ISSUE 51: else a wave of 48
    text = compiled.as_text()
    # (by the custom calls' own op_name: a kernel's serialized body may spell another's name by chance)
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    count = lambda kernel: sum(kernel in name for name in calls)  # noqa: E731
    if name == "chain_64":
        # one decode product a routed layer, one update of the state and of the tail a state-space layer
        assert (count("moe_decode"), count("ssm_update"), count("conv_update")) == (10, 9, 9)
        assert count("paged_attn") >= 1 and not count("gmm")
        # (one period: the stack's whole shape IS a layer's with a leading 1, so the program's own operand handed
        # on is let through, as ``_experts_moved`` lets a stack's through, and everything else of that shape is not)
        moved = [line for line in _experts_moved(text, 1, 36, 4096, 768)
                 if not re.search(r" (parameter|get-tuple-element|bitcast)\(", line)]
        assert not moved, moved
        rows_of_the_tail = [line.strip()[:200] for line in text.splitlines()
                            if re.search(r"= (bf16|f32)\[(1,64,25344|64,25344|64,3,8448)\]", line)]
        assert not rows_of_the_tail, rows_of_the_tail
    else:
        assert count("gmm") >= 30 and not count("moe_decode") and "conv_update" not in text
    # neither pool is moved: not copied, not re-laid, not taken into the chip's fast memory and back
    state, conv = r"f32\[(9,64|1,64|64),64,128,128\]", r"bf16\[9,64,25344\]"
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= \(?(%s|%s)\S* (copy|copy-start|transpose)\(" % (state, conv), line)
             or re.search(r"(f32\[9,64,64,128,128\]|%s)\S*S\(1\)" % conv, line)]
    assert not moved, moved
    assert not re.search(r"dynamic-update-slice\S*\.remat\S* = f32\[9,64,64,128,128\]", text)


@pytest.mark.parametrize("T,M,H,layers,glu,E", [
    (128, 2048, 512, 3, True, 64), (64, 2048, 1536, 7, True, 64), (64, 3584, 1024, 5, True, 64),
    (8, 2048, 1536, 7, True, 64), (24, 3584, 1024, 5, False, 64), (64, 4096, 768, 1, True, 36),
], ids=["qwen-128-rows", "glm-64-rows", "xing-64-rows", "glm-8-rows", "xing-24-rows-no-w_gate",
        "granite-small-64-rows-36-held"])
def test_moe_decode_compiles_at_the_cells_shapes(one_chip, monkeypatch, T, M, H, layers, glu, E):
    """The decode product's kernel alone at the four routed cells' shapes (the
    experts held, the stack of the cell's routed layers or periods whole), at the
    tile of the hidden width it picks from them (384 of 768 at a model width of
    4,096: 18.9 MB of double buffers), and at ``row_bucket``'s least and an odd
    multiple of it in bf16 (half a sublane tile of rows over)."""
    from deepspeed_tpu.ops.pallas import moe_decode

    monkeypatch.setattr(moe_decode, "_interpret", lambda: False)
    assert moe_decode.takes(T, M, H, jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.bfloat16))
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    up, down = sds((layers, E, M, H)), sds((layers, E, H, M))

    def product(x, gate, w_gate, w_up, w_down, layer):
        return moe_decode.moe_decode(x, gate, w_gate if glu else None, w_up, w_down, layer,
                                     "silu_glu" if glu else "gelu")

    assert _compiled_kernels(product, sds((T, M)), sds((T, E), jnp.float32), up, up, down, sds((), jnp.int32)) == 1


def _experts_moved(text, layers, E, M, H):
    """The instructions of a compiled program that make a layer's experts
    (``[E, M, H]`` or ``[E, H, M]``, with or without a leading 1), and those
    that make the stack's whole shape and are not the program's own operand
    handed on: a copy or a slice of either would be 0.4-1.2 GB a layer-step."""
    one = r"bf16\[(1,)?%d,(%d,%d|%d,%d)\]" % (E, M, H, H, M)
    whole = r"bf16\[%d,%d,(%d,%d|%d,%d)\]" % (layers, E, M, H, H, M)
    return [line.strip()[:200] for line in text.splitlines()
            if re.search(r"= \(?%s" % one, line)
            or (re.search(r"= \(?%s" % whole, line)
                and not re.search(r" (parameter|get-tuple-element|bitcast)\(", line))]


@pytest.mark.parametrize("config,workload,stack", [
    ("glm-4.7-flash", "glm-4.7-flash.serve.batch", (7, 64, 2048, 1536)),
    ("xing4.0-29b-a4b", "xing4.0-29b-a4b.serve.long-prompt-batch", (5, 64, 3584, 1024)),
], ids=["glm", "xing"])
def test_a_routed_chain_reads_the_picked_experts_from_the_stack(one_chip, monkeypatch, config, workload, stack):
    """The two latent, routed cells' chains whole (64 rows, 8 steps, the cell's
    own pool and block table), for the described v5e: the decode product is the
    kernel ``moe_decode``, one call in the scan's body, on the stacked expert
    weights WHOLE, which the layer scan closes over and names by its index; the
    program holds no instruction of a layer's experts' shape, sliced or copied
    (1.2 GB a layer in glm, 1.4 in xing: the temporaries stay under a tenth of
    that), and fits the chip beside the weights and the pool, which comes back
    aliased."""
    import dataclasses

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import moe_decode, norms, paged_attention as pa

    for module in (pa, norms, moe_decode):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config(config))), dtype=jnp.bfloat16)
    assert (cfg.routed_layers, cfg.num_experts, cfg.hidden_size, cfg.expert_width) == stack
    engine = harness.load_workload(workload)["engine"]
    bs, rows = engine["kv_block_size"], engine["max_seqs"]
    NB = engine["kv_pool_bytes"] // (bs * cfg.num_layers * cache.latent_pool_width(cfg) * 2)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: CausalLM(cfg).init({"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                                       train=False)["params"], jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16))))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chain(params, pool, tokens, start_pos, tables, active, budgets, rng):
        return paged.ragged_decode_chain(params, cfg, pool, tokens, start_pos, tables, bs,
                                         active, budgets, rng, engine["decode_chain"], None, with_picks=True)

    compiled = chain.lower(
        params, pool, i32(rows), i32(rows), i32(rows, engine["max_seq_len"] // bs),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip), i32(rows),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    assert mem.temp_size_in_bytes < 0.12e9, mem.temp_size_in_bytes  # (0.025 and 0.072)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2 ** 30
    assert sum("tpu_custom_call" in line and "moe_decode" in line for line in text.splitlines()) == 1
    assert not _experts_moved(text, *stack)


@pytest.mark.parametrize("n,tokens,C,dtype", [
    (4, 8 * 2048, 3584, jnp.bfloat16),  # xing4.0-29b-a4b.serve.long-prompt-batch's prefill: 128 tiles of 128 tokens
    (4, 64, 3584, jnp.bfloat16),        # its chain's step: under one tile (``takes`` leaves it to XLA; it compiles)
    (4, 8240, 1792, jnp.float32),       # float32 streams at a width ``takes`` admits, a last tile of 48 tokens
], ids=["xing-prefill", "xing-chain-step", "fp32-ragged"])
def test_the_hyper_connections_kernels_compile_within_the_default_scope(one_chip, monkeypatch, n, tokens, C, dtype):
    """``mhc_mix_read`` and ``mhc_write`` (``ops/pallas/mhc.py``) for the
    described v5e: a 128-token tile of the streams, ``phi`` held once, ``u`` and
    the mix out; the streams in and out of the write-back a half of ``C`` a
    step, aliased. Neither sets ``vmem_limit_bytes``: a refusal here is the
    default 16 MiB scope's (PR 52: a kernel scoped past it takes fast memory
    from what the compiler keeps there for the whole program)."""
    from deepspeed_tpu.ops.pallas import mhc

    monkeypatch.setattr(mhc, "_interpret", lambda: False)
    K = n * n + 2 * n
    sds = lambda s, dt=dtype: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    sizes = dict(norm_eps=1e-6, iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    assert mhc.takes(n, tokens, C, dtype) == (tokens > 64)
    mix_read = jax.jit(lambda x, phi, b, alpha: mhc.mhc_mix_read(x, phi, b, alpha, **sizes)).lower(
        sds((n, tokens, C)), sds((n * C, K)), sds((K,)), sds((3,))).compile()
    write = jax.jit(mhc.mhc_write, donate_argnums=0).lower(
        sds((n, tokens, C)), sds((tokens, C)), sds((K, -(-tokens // 128) * 128), jnp.float32)).compile()
    for compiled, name in ((mix_read, "mhc_mix_read"), (write, "mhc_write")):
        text = compiled.as_text()
        assert sum("tpu_custom_call" in line and name in line for line in text.splitlines()) == 1
        assert "vmem_limit_bytes" not in text
    # the streams come back in the buffer they came in: no second array of them
    assert write.memory_analysis().alias_size_in_bytes == n * tokens * C * jnp.dtype(dtype).itemsize


def test_the_xing_prefill_holds_no_copy_of_the_streams(one_chip, monkeypatch):
    """``xing4.0-29b-a4b.serve.long-prompt-batch``'s ``(8, 2048)`` ``step``
    whole, for the described v5e: two dense layers and the scan's body hold six
    ``mhc_mix_read`` and six ``mhc_write`` (a sublayer each), the write-back on
    the scan's own carry, and no instruction copies, transposes or widens to
    float32 an array of the streams' shape ``[4, 8, 2048, 3584]`` (a kernel on a
    scan's slice has had its operand copied four times in this repo; the
    ``jax.numpy`` form kept a float32 copy of a stream, 1.8 ms a layer-call)."""
    import dataclasses

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import mhc, moe_decode, norms, paged_attention as pa

    for module in (pa, norms, moe_decode, mhc):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config("xing4.0-29b-a4b"))),
                              dtype=jnp.bfloat16)
    engine = harness.load_workload("xing4.0-29b-a4b.serve.long-prompt-batch")["engine"]
    bs, N, C = engine["kv_block_size"], engine["row_bucket"], engine["chunk_bucket"]
    assert (cfg.hc_mult, N, C, cfg.hidden_size) == (4, 8, 2048, 3584)
    NB = engine["kv_pool_bytes"] // (bs * cfg.num_layers * cache.latent_pool_width(cfg) * 2)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: CausalLM(cfg).init({"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                                       train=False)["params"], jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16))))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, pool, tokens, positions, new_lens, tables):
        return paged.ragged_forward(params, cfg, pool, tokens, positions, new_lens, tables, bs, with_picks=True)

    compiled = step.lower(params, pool, i32(N, C), i32(N, C), i32(N), i32(N, engine["max_seq_len"] // bs)).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2 ** 30
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for kernel in ("mhc_mix_read", "mhc_write"):
        made = [line for line in calls if re.match(r"\s*%?" + kernel + r"[.\d]* = ", line)]
        assert len(made) == 6, (kernel, len(made))
        assert all("/mhc/" in line for line in made)  # under the scope the cell's two readers read
    streams = r"(bf16|f32)\[(4,8,2048|4,16384),3584\]"
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= \(?%s\S* (copy|copy-start|transpose|convert)\(" % streams, line)]
    assert not moved, moved
    # nor does anything under ``mhc`` make a float32 array of ONE stream's shape (the parent's ``slice_convert_fusion``)
    widened = [line.strip()[:200] for line in text.splitlines()
               if "/mhc/" in line and re.search(r"= f32\[(1,)?(8,2048|16384),3584\]", line)]
    assert not widened, widened



# ----------------------------------------------------------- a learned indexer (glm_moe_dsa: GLM-5)
def test_the_index_kernel_compiles_at_the_cell_s_shapes(one_chip, monkeypatch):
    """``dsa_index`` at glm-5.serve.long-prompt-wave8's own shapes: one row of
    8,192 queries of 32 index heads of 128 against the 8,256 keys its block
    table holds: ONE Mosaic kernel, its scores in whole key tiles (8,704
    columns, float32), within the default 16 MiB scope."""
    from deepspeed_tpu.ops.pallas import dsa as kernel

    monkeypatch.setattr(kernel, "_interpret", lambda: False)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    text = jax.jit(kernel.index_scores).lower(
        sds((1, 8192, 32, 128), jnp.bfloat16), sds((1, 8256, 128), jnp.bfloat16), sds((1, 8192, 32), jnp.float32),
        sds((1, 8192), jnp.int32)).compile().as_text()
    (call,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert re.search(r"%dsa_index\S* = f32\[1,8192,8704\]", call), call[:300]


def test_the_select_kernel_compiles_at_the_cell_s_shapes(one_chip, monkeypatch):
    """``dsa_select`` at glm-5.serve.long-prompt-wave8's own shapes: a row's scores as ``dsa_index`` leaves them,
    ``f32[1, 8192, 8704]``, 2,048 kept, the mask out in the walk's bf16: ONE Mosaic kernel that takes the scores
    as they come (no copy, no slice of the columns) in tiles of 64 queries, 8.9 MiB by ``_select_form``'s count,
    within the default 16 MiB scope (no ``vmem_limit_bytes``); asked for bool it is the same kernel and one
    compare after it."""
    from deepspeed_tpu.ops.pallas import dsa as kernel

    monkeypatch.setattr(kernel, "_interpret", lambda: False)
    assert kernel._select_form(8192, 8704, 2) == (64, 512)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    for dtype, out in ((jnp.bfloat16, "bf16"), (jnp.bool_, "pred")):
        text = jax.jit(lambda s, p: kernel.select_mask(s, 2048, p, dtype)).lower(  # noqa: B023
            sds((1, 8192, 8704), jnp.float32), sds((1, 8192), jnp.int32)).compile().as_text()
        (call,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
        assert re.search(r"%dsa_select\S* = bf16\[1,8192,8704\]", call), call[:300]
        assert "vmem_limit" not in call.split("custom_call_config")[0]
        assert not re.search(r"= f32\[1,8192,8704\]\S* (copy|fusion|slice|pad)\(", text)
        assert re.search(r"ROOT \S+ = %s\[1,8192,8704\]" % out, text)


def _masked_latent_kernel_compiled(one_chip, monkeypatch, N, C, H):
    """``dsa_paged_attn`` at the glm-5 cell's pool (1 GiB of 6 layers), table (516 pages) and mask width."""
    return _latent_kernel_compiled(one_chip, monkeypatch, N, C, H, 43686, 516, 1 / 16, mask_columns=8704)


@pytest.mark.parametrize("N,C,steps,rows,pages", [(1, 8192, 512, 1024, 32), (2, 64, 8, 1024, 16)],
                         ids=["cell-row-8192", "two-rows-64"])
def test_the_latent_kernel_under_a_mask_compiles_at_64_heads(one_chip, monkeypatch, N, C, steps, rows, pages):
    """``dsa_paged_attn`` at the glm-5 cell's shapes: 64 heads against one
    640-column slab a token, a table of 516 pages, a 1 GiB pool of 6 layers, the
    mask [N, C, 8,704] of the index kernel's width: tiles of 16 tokens, the
    rows head by head (1,024 of them; the queries go in and the output comes
    out ``[N, heads, C, .]``, which is what the instruction's shape says since
    PR 56), against chunks of 32 pages where a row brings 8,192 tokens (8
    before PR 56) and of 16 where it brings 64; ONE kernel, no copy of the
    pool, within the default Mosaic scope (no ``vmem_limit_bytes``)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    tq, ppcb = pa._masked_latent_form(C, 64, 640, 512, 2, 516, 16, 8704)
    assert (N * C // tq, 64 * tq, ppcb) == (steps, rows, pages)
    call = _masked_latent_kernel_compiled(one_chip, monkeypatch, N, C, 64)
    assert re.search(r"%%dsa_paged_attn\S* = bf16\[%d,64,%d,512\]" % (N, C), call), call[:300]
    assert "vmem_limit" not in call.split("custom_call_config")[0]


@pytest.mark.parametrize("H,tq,ppcb,fits", [(64, 16, 32, True), (64, 16, 48, True), (64, 16, 56, False),
                                            (40, 32, 24, True), (40, 32, 32, False)], ids=lambda v: str(v))
def test_the_masked_form_s_count_of_vmem_is_the_compiler_s_verdict(one_chip, monkeypatch, H, tq, ppcb, fits):
    """``_masked_latent_vmem_bytes`` against ``_LATENT_VMEM_BUDGET`` says what
    the chip's compiler says of a tile and a chunk under the glm-5 cell's mask,
    on both sides of the edge at two head counts and the cell's 8,192 tokens
    a row (a shorter row leaves the compiler more room: (16, 56) fits at
    4,096, so there the count errs to the safe side): the form
    ``_masked_latent_form`` picks at 64 heads, (16, 32), is the last doubling
    that fits; (16, 48) fits too and was no faster on the chip (PERF.md,
    PR 56)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    columns = max(8704, -(-516 // ppcb) * ppcb * 16)
    assert (pa._masked_latent_vmem_bytes(H, tq, ppcb * 16, 640, 512, 2, columns) <= pa._LATENT_VMEM_BUDGET) is fits
    monkeypatch.setattr(pa, "_masked_latent_form", lambda *shapes: (tq, ppcb))
    if fits:
        _masked_latent_kernel_compiled(one_chip, monkeypatch, 1, 8192, H)
    else:
        with pytest.raises(Exception, match="vmem"):
            _masked_latent_kernel_compiled(one_chip, monkeypatch, 1, 8192, H)


@pytest.mark.parametrize("name", ["prefill_2x8192", "check_4x8192", "check_4x1", "chain_8"])
def test_glm_5_programs_compile_at_the_cell_s_shapes(one_chip, monkeypatch, name):
    """``glm-5.serve.long-prompt-wave8``'s programs whole, for the described v5e
    at the cell's own shapes (1 dense + 5 routed layers at the published
    widths, 64 heads, the indexer's 32 heads of 128 keeping 2,048, 16 of 256
    experts held, a 1 GiB pool of latents AND index keys on one block table of
    516 pages), with the picks handed out as the timed path hands them: the
    ``(2, 8192)`` prefill (``dsa_index``, ``dsa_select`` and
    ``dsa_paged_attn`` once in the dense layer and once in the scan's body,
    the scores going from the first to the second and the bf16 mask from the
    second to the third as they are, no copy and no conversion between; the
    share's sorted dispatch through megablox ``gmm``), the check's ``(4, 8192)`` and ``(4, 1)`` steps
    (``runners/serve.py::check`` feeds four prompts through ``put``: their
    attention goes a row of 8,192 at a time, ``_ATTEND_GROUP_TOKENS``, or the
    four rows' absorbed queries alone are 2.7 GB) and the chain of 8 steps at 8
    rows (one token a row: XLA's index scores, ``lax.top_k``, the kept rows
    gathered; ``moe_decode`` on the stacked experts). Each fits the chip
    beside the weights and the pool with room to spare, returns the donated
    pool aliased (both arrays), and copies neither."""
    import dataclasses
    import json

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, model, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import dsa as dsa_kernel, flash_attention as fa, moe_decode, norms, paged_attention as pa

    for module in (pa, fa, norms, moe_decode, dsa_kernel):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    monkeypatch.setattr(model, "_grouped_matmul", lambda lhs, rhs, sizes: model._gmm_padded(lhs, rhs, sizes))
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config("glm-5"))), dtype=jnp.bfloat16)
    assert (cfg.num_experts, cfg.router_experts, cfg.index_topk, cfg.num_heads) == (16, 256, 2048, 64)
    engine = harness.load_workload("glm-5.serve.long-prompt-wave8")["engine"]
    bs = engine["kv_block_size"]
    per_token = cfg.num_layers * 2 * (cache.latent_pool_width(cfg) + cache.index_pool_width(cfg))
    assert per_token == 9216
    NB, table = engine["kv_pool_bytes"] // (bs * per_token), -(-engine["max_seq_len"] // bs)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), CausalLM(cfg).init(
            {"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]),
        jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16))))
    assert pool.kv.k.shape == (43686, 16, 640) and pool.kv.v.shape == (43686, 16, 128) and table == 516
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if name == "chain_8":
        rows = engine["max_seqs"]

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pool, tokens, start_pos, tables, active, budgets, rng):
            return paged.ragged_decode_chain(params, cfg, pool, tokens, start_pos, tables, bs,
                                             active, budgets, rng, engine["decode_chain"], None, with_picks=True)

        args = (i32(rows), i32(rows), i32(rows, table), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
                i32(rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    else:
        rows, chunk = map(int, name.partition("_")[2].split("x"))

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pool, tokens, positions, new_lens, tables):
            return paged.ragged_forward(params, cfg, pool, tokens, positions, new_lens, tables, bs, with_picks=True)

        args = (i32(rows, chunk), i32(rows, chunk), i32(rows), i32(rows, table))
    compiled = program_.lower(params, pool, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    assert pool_bytes == 43686 * 16 * 768 * 2 and mem.alias_size_in_bytes >= pool_bytes
    peak_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    print(json.dumps({"program": name, "temp_gb": mem.temp_size_in_bytes / 1e9,
                      "argument_gb": mem.argument_size_in_bytes / 1e9, "peak_gib": peak_gib}))
    assert peak_gib < 14.5  # of 15.75: the check's four rows stand at 14.1, the timed prefill at 13.3
    text = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    count = lambda kernel: sum(kernel in name for name in calls)  # noqa: E731
    if chunk_of(name) > 1:
        assert (count("dsa_index"), count("dsa_select"), count("dsa_paged_attn")) == (2, 2, 2) and count("gmm") >= 3
        # the scores and the mask go from kernel to kernel as they are: nothing else makes an array of their shape
        # (a fusion's inner instructions are no arrays: the counters' reduce reads the mask and writes two numbers)
        unfused = re.sub(r"(?ms)^%fused_computation\S* \(.*?^}$", "", text)
        between = [line.strip()[:200] for line in unfused.splitlines()
                   if re.search(r"= (f32|s32|bf16|pred)\[1,8192,8704\]", line) and "tpu_custom_call" not in line]
        assert not between, between
        assert not count("mla_paged_attn") and not count("moe_decode")
    else:  # one token a row: no kernel of the indexer's, the kept rows gathered
        assert not count("dsa_index") and not count("dsa_paged_attn") and not count("mla_paged_attn")
        assert not any(name.endswith("dsa_select") for name in calls)  # (the scope is there: ``lax.top_k``)
        assert "dsa_select" in text and "dsa_attend" in text
        if name == "chain_8":
            assert count("moe_decode") == 1
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= \(?bf16\[43686,16,(640|128)\]\S* (copy|copy-start|transpose)\(", line)
             or re.search(r"bf16\[43686,16,(640|128)\]\S*S\(1\)", line)]
    assert not moved, moved


@pytest.mark.parametrize("told", [False, True], ids=["bucket", "lengths"])
@pytest.mark.parametrize("window,cells", [(4096, 252), (None, 528)], ids=["band-4096", "causal"])
def test_the_banded_flash_forward_compiles_at_128_heads_over_8(one_chip, monkeypatch, window, cells, told):
    """``command-a-plus-05-2026.serve.long-prompt-wave8``'s prefill attention: a
    ``(1, 16384)`` prompt, 128 query heads over 8 key-value heads of 128 (GQA in
    the index maps), under a band of 4,096 keys (kernel ``swa_flash_fwd``, a
    grid of the band's 252 cells a head) and under the causal mask alone
    (``flash_fwd``, the triangle's 528): ONE Mosaic kernel each. Told the row's
    live length (PR 58) the kernel has a traced third extent, its first
    operand, and after the grid's enumeration what a step fetches (two more
    maps) and the row's live blocks and tokens; not told, the operands it had."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 16384, 128, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 8, 128), jnp.bfloat16, sharding=one_chip)
    lengths = [jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)] * told
    banded = {} if window is None else {"window": window}
    text = jax.jit(lambda q, k, v, *told: fa.flash_causal_attention(q, k, v, **banded, **dict(zip(("lengths",), told)))
                   ).lower(q, kv, kv, *lengths).compile().as_text()
    (kernel,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert ("swa_flash_fwd" in kernel) == (window is not None)
    maps = fa._tri_maps(32) if window is None else fa._band_maps(32, 512, window)
    assert len(maps[0]) == cells and f"s32[{cells}]" in kernel  # the grid's enumeration is the kernel's operand
    scalars = re.search(r"operand_layout_constraints=\{(.*?)bf16\[", kernel).group(1)
    assert re.findall(r"s32\[(\d*)\]", scalars) == ([""] + [str(cells)] * 4 + ["2"] if told else [str(cells)] * 2)


@pytest.mark.parametrize("columns,banded", [(257, True), (1032, False)], ids=["ring-257", "global-1032"])
def test_the_paged_kernel_walks_a_ring_and_a_global_table_at_8_rows(one_chip, monkeypatch, columns, banded):
    """The same cell's decode attention at 8 rows, one token a row, 128 heads
    over 8: a sliding layer's ring of 257 columns with a first live slot a row
    (kernel ``swa_paged_attn``: a third scalar operand, a second mask) and the
    full layer's 1,032 global columns (``paged_attn`` as every model runs it)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_interpret", lambda: False)
    shapes, bs = _paged_shapes(one_chip, 8, 1, False, 128, 8, 128, pages=columns, num_blocks=2056, layers=3)
    low = [jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)] * banded

    def fn(q, pk, pv, bt, qpos, lens, *low):
        return pa.flash_decode_paged(q, pk, pv, bt, qpos, bs, new_lens=lens, **dict(zip(("first_live",), low)))

    text = jax.jit(fn).lower(*shapes, *low).compile().as_text()
    (kernel,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert ("swa_paged_attn" in kernel) == banded and ("paged_attn" in kernel)


@pytest.mark.parametrize("name", ["prefill_1x16384", "chain_8"])
def test_command_a_plus_programs_compile_at_the_cell_s_shapes(one_chip, monkeypatch, name):
    """``command-a-plus-05-2026.serve.long-prompt-wave8``'s programs whole, for
    the described v5e at the cell's own shapes (one period of 3 sliding + 1
    full layers at the published widths, 128 heads over 8, a window of 4,096,
    16 of 128 experts held, 1 GiB of pages in two classes: 10,216 global pages
    a full layer and a ring of 8 x 257 a sliding layer, a block table of 1,032
    + 257 columns), with the picks handed out as the timed path hands them:
    the ``(1, 16384)`` prefill (``swa_flash_fwd`` three times and ``flash_fwd``
    once in the period's body, each told the row's live length, ``new_lens``;
    the share's sorted dispatch through megablox ``gmm``) and the chain of 8
    steps at 8 rows (``swa_paged_attn`` x 3, ``paged_attn`` x 1, ``moe_decode``
    x 4). Each fits the chip beside the weights and both pools, returns both
    donated pools aliased, and copies neither."""
    import dataclasses
    import json

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, model, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import flash_attention as fa, moe_decode, norms, paged_attention as pa

    for module in (pa, fa, norms, moe_decode):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    monkeypatch.setattr(model, "_grouped_matmul", lambda lhs, rhs, sizes: model._gmm_padded(lhs, rhs, sizes))
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config("command-a-plus-05-2026"))),
                              dtype=jnp.bfloat16)
    assert (cfg.num_experts, cfg.router_experts, cfg.sliding.window, cfg.num_heads, cfg.kv_heads) == (16, 128, 4096, 128, 8)
    engine = harness.load_workload("command-a-plus-05-2026.serve.long-prompt-wave8")["engine"]
    bs, rows = engine["kv_block_size"], engine["max_seqs"]
    ring = paged.ring_columns(cfg.sliding.window, bs)
    token = 2 * cfg.kv_heads * cfg.dims_per_head * 2  # a token's key and value a layer, bf16
    ring_blocks = rows * ring
    NB = (engine["kv_pool_bytes"] - ring_blocks * bs * cfg.sliding_layers * token) // (bs * cfg.attention_layers * token)
    table = -(-engine["max_seq_len"] // bs) + ring
    assert (ring, ring_blocks, NB, table, token) == (257, 2056, 10216, 1289, 4096)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), CausalLM(cfg).init(
            {"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]),
        jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16), ring=cache.init_ring_pool(cfg, ring_blocks, bs, jnp.bfloat16))))
    assert pool.kv.k.shape == (10216, 16, 1024) and pool.ring.k.shape == (3 * 2056, 16, 1024)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if name == "chain_8":

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pool, tokens, start_pos, tables, active, budgets, rng):
            return paged.ragged_decode_chain(params, cfg, pool, tokens, start_pos, tables, bs,
                                             active, budgets, rng, engine["decode_chain"], None, with_picks=True)

        args = (i32(rows), i32(rows), i32(rows, table), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
                i32(rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    else:
        n, chunk = map(int, name.partition("_")[2].split("x"))

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pool, tokens, positions, new_lens, tables):
            return paged.ragged_forward(params, cfg, pool, tokens, positions, new_lens, tables, bs, with_picks=True)

        args = (i32(n, chunk), i32(n, chunk), i32(n), i32(n, table))
    compiled = program_.lower(params, pool, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    assert pool_bytes == (10216 + 3 * 2056) * 16 * 1024 * 2 * 2 <= 2 ** 30 and mem.alias_size_in_bytes >= pool_bytes
    peak_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    print(json.dumps({"program": name, "temp_gb": mem.temp_size_in_bytes / 1e9,
                      "argument_gb": mem.argument_size_in_bytes / 1e9, "peak_gib": peak_gib}))
    assert peak_gib < 14.8  # of 15.75: the prefill stands at 14.59 (5.12 GB of temporaries beside 10.54 GB held)
    text = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    count = lambda kernel: sum(kernel in name for name in calls)  # noqa: E731
    if name == "chain_8":
        assert (count("swa_paged_attn"), count("paged_attn") - count("swa_paged_attn")) == (3, 1)
        assert count("moe_decode") == 4 and not count("flash_fwd")
    else:  # (a call of fresh prompts attends inside its chunks alone: no paged kernel, no one-token path)
        assert (count("swa_flash_fwd"), count("flash_fwd") - count("swa_flash_fwd")) == (3, 1) and count("gmm") >= 3
        assert not count("paged_attn")
        # PR 58: the four flash forwards are told ``new_lens``: a traced extent, then the grid's enumeration, what
        # a step fetches and the row's live blocks and tokens (``_live_grid``), under the names they had; the
        # program stands where the parent's stood (14.5865 GiB) and makes no layout copy of q, k, v or the
        # attention output that the parent did not make
        flash = [line for line in text.splitlines() if "tpu_custom_call" in line and "flash_fwd" in line]
        assert len(flash) == 4 and all(re.search(r"constraints=\{s32\[\], (?:s32\[(252|528)\]\{0\}, ){4}s32\[2\]\{0\}, ", line)
                                       and len(set(re.findall(r"s32\[(252|528)\]", line))) == 1 for line in flash)
        assert peak_gib < 14.59
        made = lambda shape, ops: len(re.findall(r"= bf16\[%s\]\S* (?:%s)\(" % (shape, ops), text))  # noqa: E731
        assert made("1,(?:128,16384|16384,128),128", "copy|fusion") <= 7 and made("1,(?:8,16384|16384,8),128", "copy") <= 3
    assert all("/swa/" in name for name in calls if "/swa_" in name)  # the new kernels under the new scope
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= \(?bf16\[(10216|6168),16,1024\]\S* (copy|copy-start|transpose)\(", line)
             or re.search(r"bf16\[(10216|6168),16,1024\]\S*S\(1\)", line)]
    assert not moved, moved


@pytest.mark.parametrize("kind", ["sliding", "global"])
def test_the_two_width_kernels_compile_at_mimo_s_shapes(one_chip, monkeypatch, kind):
    """``mimo-v2.5.serve.long-output-wave128``'s attention kernels alone: keys of 192 columns beside values of 128,
    64 query heads over 8 kv heads under a band of 128 with a sink a head (sliding), over 4 kv heads (global). The
    flash forward over (8, 2048) prompts told their live lengths (a key block of 192 lanes, a value block of 128,
    the sinks ``[64, 1, 8]`` a head a block) and the paged kernel at 128 rows, one token a row: a ring of 9 columns
    rolled under a first live slot, the global table's 194; every (query row, kv head) pair a row of ONE
    block-diagonal query (64 rows), so no product slices a head's 192 lanes out of a page."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa, paged_attention as pa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    sliding = kind == "sliding"
    Hkv, columns = (8, 9) if sliding else (4, 194)
    sds = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    sink = [sds((64,), jnp.float32)] * sliding

    def prefill(q, k, v, lengths, *sink):
        return fa.flash_causal_attention(q, k, v, lengths=lengths, **({"window": 128} if sliding else {}),
                                         **dict(zip(("sink",), sink)))

    text = jax.jit(prefill).lower(sds((8, 2048, 64, 192)), sds((8, 2048, Hkv, 192)), sds((8, 2048, Hkv, 128)),
                                  sds((8,), jnp.int32), *sink).compile().as_text()
    (kernel,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert ("swa_flash_fwd" in kernel) == sliding and "bf16[8,64,2048,128]" in kernel  # the output at the value's width
    assert ("f32[64,1,8]" in kernel) == sliding  # the sinks ride as the slopes do

    def decode(q, pk, pv, bt, qpos, lens, *rest):
        named = dict(zip(("first_live", "sink"), rest))
        return pa.flash_decode_paged(q, pk, pv, bt, qpos, 16, new_lens=lens, **named)

    pages = 128 * columns
    shapes = [sds((128, 1, 64, 192)), sds((pages, 16, Hkv * 192)), sds((pages, 16, Hkv * 128)),
              sds((128, columns), jnp.int32), sds((128, 1), jnp.int32), sds((128,), jnp.int32),
              *[sds((128, 1), jnp.int32), sds((64,), jnp.float32)] * sliding]
    text = jax.jit(decode).lower(*shapes).compile().as_text()
    (kernel,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert ("swa_paged_attn" in kernel) == sliding and "paged_attn" in kernel
    assert f"bf16[128,{64 // Hkv},{Hkv * 128}]" in kernel  # [rows, (c, g) pairs, kv heads x the value's 128]
    # the chunk the rule gives (PR 62), in the slots of K and of V: all 9 columns of the ring (80 KiB a page: one
    # chunk, T = 144), 24 pages of the global table's 40 KiB; and no bounds check on a page's copies, whose four
    # halts a page were more scalar code than the copies (the tables keep the pool's edge: test_ragged_state)
    (call,) = [e for e in jax.make_jaxpr(decode)(*shapes).jaxpr.eqns if e.primitive.name == "pallas_call"]
    chunk = 9 if sliding else 24
    assert [v.aval.shape for v in call.params["jaxpr"].invars if len(v.aval.shape) == 4] == [
        (2, chunk, 16, Hkv * 192), (2, chunk, 16, Hkv * 128)]
    assert '"disable_bounds_checks":true' in kernel


@pytest.mark.parametrize("name", ["prefill_8x2048", "chain_128"])
def test_mimo_v2_5_programs_compile_at_the_cell_s_shapes(one_chip, monkeypatch, name):
    """``mimo-v2.5.serve.long-output-wave128``'s programs whole, for the described v5e at the cell's own shapes (the
    leading dense global layer, then one period of 4 sliding + 1 global + 1 sliding routed layers at the published
    widths, 16 of 256 experts held, 2.5 GB of pages in two classes of two geometries: 24,832 global pages of 40 KiB
    a global layer and a ring of 128 x 9 pages of 80 KiB a sliding layer, a block table of 194 + 9 columns), with
    the picks handed out as the timed path hands them: the ``(8, 2048)`` prefill (``swa_flash_fwd`` five times,
    ``flash_fwd`` twice, each told the rows' live lengths) and the chain of 8 steps at 128 rows (``swa_paged_attn``
    x 5, ``paged_attn`` x 2, ``moe_decode`` x 6). Each fits the chip beside the weights and both pools, returns
    both donated pools aliased, and copies neither; the peaks are the workload file's ``assumed.compiled_peak``."""
    import dataclasses
    import json

    from benchmarks.lib import harness, program
    from deepspeed_tpu.checkpoint.hf import config_from_hf
    from deepspeed_tpu.inference import cache, model, paged
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import flash_attention as fa, moe_decode, norms, paged_attention as pa

    for module in (pa, fa, norms, moe_decode):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    monkeypatch.setattr(model, "_grouped_matmul", lambda lhs, rhs, sizes: model._gmm_padded(lhs, rhs, sizes))
    cfg = dataclasses.replace(config_from_hf(program.published(harness.load_config("mimo-v2.5"))), dtype=jnp.bfloat16)
    workload = harness.load_workload("mimo-v2.5.serve.long-output-wave128")
    engine = workload["engine"]
    bs, rows = engine["kv_block_size"], engine["max_seqs"]
    plan = cache.cache_plan(cfg, bs, engine["max_seq_len"])
    ring_blocks = rows * plan.ring_columns
    NB = (engine["kv_pool_bytes"] - plan.ring_bytes(ring_blocks, jnp.bfloat16)) // (bs * plan.bytes_per_token(jnp.bfloat16))
    table = plan.max_pages
    assert (plan.ring_columns, ring_blocks, NB, table) == (9, 1152, 24832, 203)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), CausalLM(cfg).init(
            {"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]),
        jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: plan.init(NB, ring_blocks, rows, jnp.bfloat16)))
    assert (pool.kv.k.shape, pool.kv.v.shape) == ((2 * 24832, 16, 768), (2 * 24832, 16, 512))
    assert (pool.ring.k.shape, pool.ring.v.shape) == ((5 * 1152, 16, 1536), (5 * 1152, 16, 1024))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if name == "chain_128":

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pool, tokens, start_pos, tables, active, budgets, rng):
            return paged.ragged_decode_chain(params, cfg, pool, tokens, start_pos, tables, bs,
                                             active, budgets, rng, engine["decode_chain"], None, with_picks=True)

        args = (i32(rows), i32(rows), i32(rows, table), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
                i32(rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    else:
        n, chunk = map(int, name.partition("_")[2].split("x"))
        assert [n, chunk] == workload["warm"]["prefill"][0] and n * chunk == engine["max_ragged_batch_size"]

        @functools.partial(jax.jit, donate_argnums=(1,))
        def program_(params, pool, tokens, positions, new_lens, tables):
            return paged.ragged_forward(params, cfg, pool, tokens, positions, new_lens, tables, bs, with_picks=True)

        args = (i32(n, chunk), i32(n, chunk), i32(n), i32(n, table))
    compiled = program_.lower(params, pool, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    assert pool_bytes == engine["kv_pool_bytes"] == 2_506_096_640 and mem.alias_size_in_bytes >= pool_bytes
    peak_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2 ** 30
    print(json.dumps({"program": name, "temp_gb": mem.temp_size_in_bytes / 1e9,
                      "argument_gb": mem.argument_size_in_bytes / 1e9, "peak_gib": peak_gib}))
    # of 15.75 GiB: the prefill stands at 11.14 (2.59 GB of temporaries beside 9.37 GB held), the chain at 8.75
    assert peak_gib < (11.3 if name.startswith("prefill") else 8.9)
    said = workload["assumed"]["compiled_peak"]
    assert ("%.2f GiB" % peak_gib) in said, (peak_gib, said)  # the reading the workload file states
    text = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    count = lambda kernel: sum(kernel in name for name in calls)  # noqa: E731
    if name == "chain_128":
        assert (count("swa_paged_attn"), count("paged_attn") - count("swa_paged_attn")) == (5, 2)
        assert count("moe_decode") == 6 and not count("flash_fwd")
    else:  # (a call of fresh prompts attends inside its chunks alone: no paged kernel, no one-token path)
        assert (count("swa_flash_fwd"), count("flash_fwd") - count("swa_flash_fwd")) == (5, 2) and count("gmm") >= 3
        assert not count("paged_attn")
    assert all("/swa/" in name for name in calls if "/swa_" in name)  # the sliding kind's kernels under its scope
    assert all("/attn_full/" in name for name in calls if name.endswith(("/flash_fwd", "/paged_attn")))
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= \(?bf16\[(49664|5760),16,(768|512|1536|1024)\]\S* (copy|copy-start|transpose)\(", line)
             or re.search(r"bf16\[(49664|5760),16,(768|512|1536|1024)\]\S*S\(1\)", line)]
    assert not moved, moved


def chunk_of(name: str) -> int:
    return 1 if name.startswith("chain") else int(name.rpartition("x")[2])


def test_the_norm_kernel_takes_fewer_rows_a_block_at_a_hidden_width_of_6144(one_chip, monkeypatch):
    """``rms_norm`` at [16384, 6144] in bf16: 256 rows a block stood 2.1 MiB over Mosaic's 16 MiB scope (the first
    thing the glm-5 prefill's compile refused); 128 fit. At 4,096 columns and under the block is the 256 it was."""
    from deepspeed_tpu.ops.pallas import norms

    assert [norms._row_blocks(16384, w) for w in (2048, 4096, 6144, 8192)] == [256, 256, 128, 128]
    monkeypatch.setattr(norms, "_interpret", lambda: False)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)  # noqa: E731
    assert _compiled_kernels(lambda x, s: norms.pallas_rms_norm(x, s, 1e-5), sds((16384, 6144)), sds((6144,))) == 1


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["bf16", "int8"])
def test_decode_chain_updates_the_pool_in_place(one_chip, monkeypatch, kv_quant):
    """Whether a carried array is updated in place is the chip's compiler's
    decision, not the jaxpr's (ISSUE 26). The whole decode chain at head_dim
    128, compiled for the described v5e: the donated pool comes back aliased,
    the temporaries stay far under one pool (a second pool, or a layer sliced
    out of it, would not), and the kernel reads the pool's own rank-3 array."""
    import re

    from deepspeed_tpu.inference import cache, paged
    from deepspeed_tpu.models import CausalLM, TransformerConfig
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    cfg = TransformerConfig(vocab_size=1024, hidden_size=512, intermediate_size=1024, num_layers=4,
                            num_heads=4, num_kv_heads=4, max_seq_len=512, dtype=jnp.bfloat16)
    NB, bs, rows, k = 2048, 16, 8, 2
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda key: CausalLM(cfg).init({"params": key}, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                                       train=False)["params"], jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: cache.Pools(cache.init_pool(cfg, NB, bs, jnp.bfloat16, kv_quant=kv_quant))))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chain(params, pool, tokens, start_pos, tables, active, budgets, rng):
        return paged.ragged_decode_chain(params, cfg, pool, tokens, start_pos, tables, bs,
                                         active, budgets, rng, k, None)

    compiled = chain.lower(
        params, pool, i32(rows), i32(rows), i32(rows, cfg.max_seq_len // bs),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip), i32(rows),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    values = pool.kv.k.size * pool.kv.k.dtype.itemsize  # one of the pool's two value arrays
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pool))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < values // 4, (mem.temp_size_in_bytes, values)
    text = compiled.as_text()
    dt = "s8" if kv_quant else "bf16"
    whole = r"%s\[%d,%d,%d\]" % (dt, cfg.num_layers * NB, bs, cfg.kv_heads * cfg.dims_per_head)
    kernel = next(line for line in text.splitlines()
                  if "tpu_custom_call" in line and "paged_attn" in line)
    assert len(re.findall(whole, kernel.split("operand_layout_constraints")[1])) == 2, kernel
    # nothing copies, slices or re-lays the pool's values or a layer of them
    # (the scales, 4/hd of the bytes, answer to the temporaries' bound above:
    # stored [.., bs, kvH] they pad kvH to 128 lanes and are re-laid every
    # layer, which read 69.8 MB of temporaries here beside a 67 MB pool)
    layer = r"%s\[(%d|%d),%d,%d\]" % (dt, cfg.num_layers * NB, NB, bs,
                                        cfg.kv_heads * cfg.dims_per_head)
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|dynamic-slice|reshape|transpose)\(" % layer, line)]
    assert not moved, moved


def test_layer_norm_gpt2_width(one_chip, monkeypatch):
    from deepspeed_tpu.ops.pallas import norms

    monkeypatch.setattr(norms, "_interpret", lambda: False)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    E = GPT2["E"]
    n = _compiled_kernels(norms.pallas_layer_norm, sds((8, 1024, E), jnp.bfloat16),
                          sds((E,), jnp.float32), sds((E,), jnp.float32))
    assert n == 1


def test_rms_norm_partitions_over_four_chips(topo, monkeypatch):
    """The per-row norm kernels ride the same per-shard wrapper as flash."""
    from deepspeed_tpu.ops import registry, rms_norm
    from deepspeed_tpu.ops.pallas import norms
    from deepspeed_tpu.topology.mesh import build_mesh, set_mesh

    monkeypatch.setattr(norms, "_interpret", lambda: False)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    mesh = build_mesh(devices=topo.devices, axis_sizes={"fsdp": 4})
    set_mesh(mesh)
    x = jax.ShapeDtypeStruct((8, 1024, GPT2["E"]), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(("dp", "fsdp"))))
    scale = jax.ShapeDtypeStruct((GPT2["E"],), jnp.float32, sharding=NamedSharding(mesh, P()))
    assert _compiled_kernels(rms_norm, x, scale) == 1


