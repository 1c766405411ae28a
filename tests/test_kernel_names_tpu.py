"""The Pallas kernels of the training cells, compiled for a described (not
attached) TPU v5e at the cells' own shapes: what Mosaic and the TPU lowering
accept, and that every kernel arrives in the compiled program under the name
``utils/telemetry_names.py:KERNEL_NAMES`` registers — the instruction name a
profiler trace shows (docs/DESIGN.md §9, PERF.md §3).

Nothing runs here, so this says nothing about results or times. The topology
is described inside a fixture and nowhere at import: only one process may
load the TPU's library, and under several workers only the worker that is
given THIS file may try (``on-chip-measurement`` guide §2). Keep every such
compile in this one file.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dalle_pytorch_tpu.ops import block_sparse_attention as bs
from dalle_pytorch_tpu.ops import masks as masks_lib
from dalle_pytorch_tpu.ops import ssm
from dalle_pytorch_tpu.ops.flash_attention import (
    StaticMask,
    flash_attention,
    fused_qkv_attention,
)
from dalle_pytorch_tpu.utils.telemetry_names import KERNEL_NAMES

# train-d12sparse-posemb-b8: batch 8, 16 heads of 64, 256 + 1024 positions
B, H, D, TEXT, FMAP = 8, 16, 64, 257, 32
N = TEXT + FMAP * FMAP - 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _axial_row_mask():
    return masks_lib.axial_mask(TEXT, FMAP, axis=0)[:N, :N]


def _packed(qkv):
    # the route of the cell's ``full`` and ``axial_col`` layers
    return fused_qkv_attention(qkv, None, H, D, None, True, None, D**-0.5, False)


def _blocked(q, k, v):
    pattern = StaticMask(_axial_row_mask())
    return flash_attention(q, k, v, None, True, pattern, D**-0.5, 256, 256, False)


def _pair_grid(q, k, v):
    # the route of the cell's ``axial_row`` and ``conv_like`` layers
    layout = bs.compile_block_layout(_axial_row_mask(), 128, 128)
    return bs.block_sparse_attention(q, k, v, layout, sm_scale=D**-0.5, interpret=False)


# train-granite4hmicro-d10-s8k: 1 row of 8192, 64 state-space heads of 64,
# state 128, chunks of 256
SSM_N, SSM_H, SSM_P, SSM_STATE, SSM_CHUNK = 8192, 64, 64, 128, 256
F32 = jnp.float32


def _ssd(x, dt, cum, Bm, Cm, starts):
    # the two kernel calls of ``ssm._ssd_scan_kernels``, compiled not interpreted
    states = ssm.ssd_chunk_states(x, dt, cum, Bm, SSM_CHUNK, SSM_P, False)
    row = (cum - jnp.log(dt)).transpose(0, 2, 1)
    every = jnp.ones((1, 1, SSM_H * SSM_P), F32)
    return ssm.ssd_chunk_outputs(
        x, cum, row, Bm, Cm, starts + states, every, SSM_CHUNK, SSM_P, False
    )


def _conv(xbc, taps, bias):
    # the call of ``ssm.conv_silu``, compiled not interpreted, under ``remat``
    # as the cell's blocks run it
    pieces = jax.checkpoint(
        lambda *operands: ssm.ssm_conv(*operands, (SSM_H * SSM_P, SSM_STATE, SSM_STATE), False)
    )(xbc, taps, bias)
    return jnp.concatenate(pieces, axis=-1)


SSM_CONV = SSM_H * SSM_P + 2 * SSM_STATE

# train-joyaiflash-d6-ep16-s4k: 4 rows of 4096, 32 heads, queries and keys of
# 128 + 64 against values of 128, blocks of 1024 (``_flash_block(4096)``)
MLA_B, MLA_H, MLA_N, MLA_QK, MLA_V = 4, 32, 4096, 192, 128


def _latent(q, k, v):
    # the call of ``ops/attention.py:LatentAttention``: a value width of its own
    out = flash_attention(q, k, v, None, True, None, MLA_QK**-0.5, 1024, 1024, False)
    assert out.shape == (MLA_B, MLA_H, MLA_N, MLA_V)
    return out


# train-qwen3next-d4-ep16-s8k: 2 rows of 8192, 16 key and 32 value heads of
# 128, chunks of 64; the convolution over q | k | v WITHOUT a bias; gated
# attention on the blocked flash kernels at head 256
GDN_B, GDN_N, GDN_HK, GDN_HV, GDN_D, GDN_CHUNK = 2, 8192, 16, 32, 128, 64
GDN_TABLE = ((GDN_B, GDN_HV, GDN_N // GDN_CHUNK, GDN_CHUNK), F32)
GDN_CONV = (2 * GDN_HK + GDN_HV) * GDN_D


def _delta(q, k, v, g, beta):
    # the call of ``gdn.gated_delta_rule``, compiled not interpreted
    from dalle_pytorch_tpu.ops import gdn

    return gdn.delta_rule_chunks(q, k, v, -jnp.abs(g), jax.nn.sigmoid(beta), GDN_HK, False)


def _conv_no_bias(qkv, taps):
    sizes = (GDN_HK * GDN_D, GDN_HK * GDN_D, GDN_HV * GDN_D)
    assert ssm.ssm_conv_kernel_eligible(GDN_N, sizes, 4)
    pieces = ssm.ssm_conv(qkv, taps, jnp.zeros((GDN_B, 1, GDN_CONV), F32), sizes, False)
    return jnp.concatenate(pieces, axis=-1)


def _gated(q, k, v):
    return flash_attention(q, k, v, None, True, None, 256**-0.5, 1024, 1024, False)


# train-smallthinker-d4-ep4-s16k's window layers: 28 heads of 128 over 16,384
# positions, a window of 4,096 keys, block 1,024 (a banded grid of 5 key blocks)
def _windowed(q, k, v):
    return flash_attention(q, k, v, None, True, None, 128**-0.5, 1024, 1024, False, 4096)


# train-kimilinear-d5-ep32-s16k: 1 row of 16,384, 32 heads of 128 (keys and
# values), chunks of 64, the decay per key channel
KDA_N, KDA_H, KDA_D, KDA_CHUNK = 16384, 32, 128, 64


def _kda(q, k, v, g, beta):
    # the call of ``kda.kimi_delta_rule``, compiled not interpreted
    from dalle_pytorch_tpu.ops import kda

    g = kda.chunk_log_decay(-jnp.abs(g), KDA_CHUNK)
    return kda.kda_chunks(q, k, v, g, jax.nn.sigmoid(beta), False)


ROUTES = {
    "kda_delta_rule": (
        _kda,
        [(1, KDA_N, KDA_H * KDA_D)] * 3 + [((1, KDA_N, KDA_H * KDA_D), F32),
                                            ((1, KDA_H, KDA_N // KDA_CHUNK, KDA_CHUNK), F32)],
        {"kda_chunk_tables", "kda_chunk_fwd", "kda_chunk_bwd"},
    ),
    "delta_rule": (
        _delta,
        [(GDN_B, GDN_N, GDN_HK * GDN_D)] * 2 + [(GDN_B, GDN_N, GDN_HV * GDN_D), GDN_TABLE, GDN_TABLE],
        {"gdn_chunk_tables", "gdn_chunk_fwd", "gdn_chunk_bwd"},
    ),
    "ssm_conv_no_bias": (
        _conv_no_bias, [(GDN_B, GDN_N, GDN_CONV), ((GDN_B, 4, GDN_CONV), F32)],
        {"ssm_conv_fwd", "ssm_conv_bwd"},
    ),
    "gated_flash_head256": (
        _gated, [(GDN_B, 16, GDN_N, 256)] * 3, {"flash_fwd", "flash_bwd"},
    ),
    "windowed_flash": (_windowed, [(1, 28, 16384, 128)] * 3, {"flash_fwd", "flash_bwd"}),
    "ssm_conv": (
        _conv,
        [(1, SSM_N, SSM_CONV), ((1, 4, SSM_CONV), F32), ((1, 1, SSM_CONV), F32)],
        {"ssm_conv_fwd", "ssm_conv_bwd"},
    ),
    "ssd_scan": (
        _ssd,
        [(1, SSM_N, SSM_H * SSM_P), ((1, SSM_N, SSM_H), F32), ((1, SSM_N, SSM_H), F32),
         (1, SSM_N, SSM_STATE), (1, SSM_N, SSM_STATE),
         ((1, SSM_N // SSM_CHUNK, SSM_STATE, SSM_H * SSM_P), F32)],
        {"ssd_state_fwd", "ssd_state_bwd", "ssd_chunk_fwd", "ssd_chunk_bwd"},
    ),
    "latent_flash": (
        _latent, [(MLA_B, MLA_H, MLA_N, MLA_QK)] * 2 + [(MLA_B, MLA_H, MLA_N, MLA_V)],
        {"flash_fwd", "flash_bwd"},
    ),
    "packed_flash": (_packed, [(B, N, 3 * H * D)], {"flash_qkv_fwd", "flash_qkv_bwd"}),
    "blocked_flash": (_blocked, [(B, H, N, D)] * 3, {"flash_fwd", "flash_bwd"}),
    "pair_grid": (
        _pair_grid, [(B, H, N, D)] * 3,
        {"block_sparse_fwd", "block_sparse_dq", "block_sparse_dkv"},
    ),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_kernels_compile_for_v5e_under_their_registered_names(route, one_chip):
    fn, shapes, names = ROUTES[route]
    assert names <= KERNEL_NAMES
    # a shape alone is bfloat16; (shape, dtype) where an operand is not
    args = [
        jax.ShapeDtypeStruct(*(s if isinstance(s[0], tuple) else (s, jnp.bfloat16)), sharding=one_chip)
        for s in shapes
    ]

    def loss(*xs):
        return jnp.sum(fn(*xs).astype(jnp.float32))

    grad = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(len(args)))))
    text = grad.lower(*args).compile().as_text()
    # a Pallas call's instruction is named after the innermost component of
    # its name stack, the kernel's ``name=`` (here wrapped as
    # ``jvp_<name>_``, in the whole step plain ``<name>.<n>``): what a
    # profiler trace names the event by
    kernels = re.findall(r"^\s*%([\w.\-]+) = .*tpu_custom_call", text, re.M)
    assert len(kernels) == len(names), kernels
    for name in names:
        assert any(name in k for k in kernels), f"{name} not among {kernels}"


def test_every_registered_kernel_is_named_at_its_call():
    """The registry against the source: each name is the ``name=`` of a
    ``pallas_call`` (or of a wrapper that hands it on) under ops/."""
    import pathlib

    ops = pathlib.Path(bs.__file__).parent
    named = set()
    for path in ops.glob("*.py"):
        named |= set(re.findall(r'\bname="([a-z_]+)"', path.read_text()))
    assert KERNEL_NAMES <= named, KERNEL_NAMES - named
    assert np.all([n[-1].isalpha() for n in KERNEL_NAMES])
