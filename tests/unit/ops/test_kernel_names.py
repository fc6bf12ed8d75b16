"""Every Pallas kernel of ``ops/pallas`` carries its own name into the
lowered program (``pallas_call(name=)`` opens a scope of that name, which on
the chip also names the Mosaic custom-call's instruction; PERF.md, section 3),
so a trace reader finds a kernel by name and not by operand shape."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.flash_attention import flash_causal_attention
from deepspeed_tpu.ops.pallas.norms import pallas_layer_norm, pallas_rms_norm
from deepspeed_tpu.ops.pallas.paged_attention import flash_decode_paged
from deepspeed_tpu.ops.pallas.quantizer import pallas_dequantize_int8, pallas_quantize_int8
from deepspeed_tpu.ops.pallas.sparse_attention import block_sparse_attention_pallas

QKV = jnp.ones((1, 32, 2, 16), jnp.float32)
LAYOUT = np.tril(np.ones((2, 4, 4), np.int64))


def _flash(q):
    return flash_causal_attention(q, q, q).sum()


# past the one-pass backward's VMEM budget (8,192 rows of 128 lanes), where
# the dq kernel still runs; only lowered, never run
LONG = jax.ShapeDtypeStruct((1, 8192 + 512, 1, 16), jnp.float32)


def _sparse(q):
    return block_sparse_attention_pallas(q, q, q, LAYOUT, block=8).sum()


def _paged(q):
    pool = jnp.ones((4, 8, 2 * 16), jnp.float32)  # [pages, bs, kvH*hd]
    return flash_decode_paged(q, pool, pool, jnp.zeros((2, 2), jnp.int32),
                              jnp.zeros((2, 1), jnp.int32), 8)


def _conv(x):
    from deepspeed_tpu.ops.pallas.conv_update import conv_pool_step

    return conv_pool_step(jnp.zeros((2, 8, 3 * 128), jnp.bfloat16), 1, x, jnp.ones((4, 128), jnp.bfloat16))[0]


def _ssm(x):
    from deepspeed_tpu.ops.pallas.ssm_update import ssm_pool_step

    return ssm_pool_step(jnp.zeros((2, 2, 1, 128, 128)), 1, x, jnp.ones((2, 2)), jnp.zeros((2,)),
                         jnp.ones((2, 1, 128)), jnp.ones((2, 1, 128)), jnp.ones((2,)))[0]


def _gdn(v):
    from deepspeed_tpu.ops.pallas.gdn_update import gdn_pool_step

    qk = jnp.ones((2, 1, 128))
    return gdn_pool_step(jnp.zeros((2, 2, 1, 128, 128)), 1, qk, qk, v, -jnp.ones((2, 1)), jnp.ones((2, 1)))[0]


def _moe(x):
    from deepspeed_tpu.ops.pallas.moe_decode import moe_decode

    w = jnp.ones((2, 4, 128, 128))
    return moe_decode(x, jnp.ones((8, 4)), w, w, w, 1, "silu_glu")


def _mhc(write):
    from deepspeed_tpu.ops.pallas import mhc

    def sublayer(x):
        mixed, u = mhc.mhc_mix_read(x, jnp.ones((2 * 128, 8)), jnp.ones((8,)), jnp.ones((3,)), norm_eps=1e-6, iters=2,
                                    eps=1e-6, clamp=(-30.0, 30.0))
        return mhc.mhc_write(x, u, mixed) if write else u

    return sublayer


KERNELS = [
    ("flash_fwd", _flash, (QKV,)),
    ("flash_bwd_dq", jax.grad(_flash), (LONG,)),
    ("flash_bwd_dkv", jax.grad(_flash), (QKV,)),
    ("sparse_attn_fwd", _sparse, (QKV,)),
    ("sparse_attn_bwd_dq", jax.grad(_sparse), (QKV,)),
    ("sparse_attn_bwd_dkv", jax.grad(_sparse), (QKV,)),
    ("paged_attn", _paged, (jnp.ones((2, 1, 2, 16), jnp.float32),)),
    ("rms_norm", lambda x: pallas_rms_norm(x, jnp.ones((32,))), (jnp.ones((8, 32)),)),
    ("layer_norm", lambda x: pallas_layer_norm(x, jnp.ones((32,)), jnp.zeros((32,))),
     (jnp.ones((8, 32)),)),
    ("quantize_int8", lambda x: pallas_quantize_int8(x, block_size=64), (jnp.ones((256,)),)),
    ("dequantize_int8", lambda v: pallas_dequantize_int8(v, jnp.ones((4,)), (256,), block_size=64),
     (jnp.ones((256,), jnp.int8),)),
    ("conv_update", _conv, (jnp.ones((8, 128), jnp.bfloat16),)),
    ("ssm_update", _ssm, (jnp.ones((2, 2, 64)),)),
    ("gdn_update", _gdn, (jnp.ones((2, 1, 128)),)),
    ("moe_decode", _moe, (jnp.ones((8, 128)),)),
    ("mhc_mix_read", _mhc(False), (jnp.ones((2, 16, 128)),)),
    ("mhc_write", _mhc(True), (jnp.ones((2, 16, 128)),)),
]


@pytest.mark.parametrize("name,fn,args", KERNELS, ids=[k[0] for k in KERNELS])
def test_lowered_text_carries_the_kernel_s_name(name, fn, args):
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    # a path component of an op_name; transpose(jvp(<name>)) in a backward pass
    assert re.search(r"[/(]%s[/)]" % name, text), (
        f"no op_name with the path component {name!r}")
