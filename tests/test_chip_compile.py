"""The kernels of the two main paths, compiled for the chip without the
chip: the installed TPU compiler takes a DESCRIBED ``v5e:2x2`` device, so
each test AOT-compiles one Pallas kernel at the widths ``chip_smoke.py``
serves and trains (the ``on-chip-measurement`` guide, section 2,
rehearsal 3).  Interpret mode cannot see what Mosaic refuses — the paged
decode kernels passed every interpret test while the chip's compiler
rejected their ``dot_dimension_numbers`` — so these are the tests that
guard a kernel between chip runs.  A compile that passes is not a chip
run: it says nothing about results or times.

About a second each.  Skipped where the topology cannot be described (no
libtpu).  The persistent compilation cache is turned off around them: a
TPU executable written to it here cannot be read back without a chip,
and the next run would warn and compile again."""
import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax
import jax.numpy as jnp

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def chip():
    """``sds(shape, dtype)`` placed on one described v5e chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure to describe = skip
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiles(fn, *args):
    """Lower and compile; the chip's compiler raises what it would raise
    on the chip.  Returns how many Mosaic kernels the program holds."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


# the serving shape of gpt3_1p3b() (32 x 64) and of the 1.3B flagship's
# attention (16 x 128): 8 slots, 1024 pages, 64 pages per slot
SLOTS, PAGES, MAXP = 8, 1024, 64
HEADS = [(32, 64), (16, 128)]


@pytest.mark.parametrize("nh,hd", HEADS)
@pytest.mark.parametrize("ps", [16, 32])
def test_paged_attention_fp(chip, nh, hd, ps):
    from paddle_tpu.ops.pallas.paged_attn import _paged_attention_tpu
    pool = chip((PAGES, ps, nh, hd), bf16)
    assert compiles(_paged_attention_tpu, chip((SLOTS, 1, nh, hd), bf16),
                    pool, pool, chip((SLOTS, MAXP), i32),
                    chip((SLOTS,), i32)) == 1


@pytest.mark.parametrize("nh,hd", HEADS)
def test_paged_attention_int8(chip, nh, hd, ps=32):
    from paddle_tpu.ops.pallas.paged_attn import _paged_attention_quant_tpu
    pool = chip((PAGES, ps, nh, hd), i8)
    scale = chip((PAGES, ps, nh), f32)
    assert compiles(_paged_attention_quant_tpu,
                    chip((SLOTS, 1, nh, hd), bf16), pool, scale, pool,
                    scale, chip((SLOTS, MAXP), i32),
                    chip((SLOTS,), i32)) == 1


def test_paged_attention_one_tp_shard(chip):
    """What one rank of a tp=4 engine runs: 8 of the 32 heads."""
    from paddle_tpu.ops.pallas.paged_attn import _paged_attention_tpu
    pool = chip((PAGES, 16, 8, 64), bf16)
    assert compiles(_paged_attention_tpu, chip((SLOTS, 1, 8, 64), bf16),
                    pool, pool, chip((SLOTS, MAXP), i32),
                    chip((SLOTS,), i32)) == 1


@pytest.mark.parametrize("m", [16, 256])   # decode rows (padded), prefill
def test_dequant_matmul(chip, m, k=2048, n=8192):
    from paddle_tpu.ops.pallas.dequant_matmul import _dqmm_tpu, _pick_blocks
    blocks = _pick_blocks(m, k, n, 2)
    assert blocks is not None
    fn = functools.partial(_dqmm_tpu, block_m=blocks[0], block_n=blocks[1],
                           interpret=False)
    assert compiles(fn, chip((m, k), bf16), chip((k, n), i8),
                    chip((n,), f32)) == 1


# the flagship train step's attention: batch 4, seq 2048, 16 x 128
QKV = (4, 2048, 16, 128)


def test_flash_attention_fwd(chip):
    from paddle_tpu.ops.pallas.flash_attn import _flash_attention_tpu
    q = chip(QKV, bf16)
    fn = functools.partial(_flash_attention_tpu, causal=True,
                           return_lse=True)
    assert compiles(fn, q, q, q) == 1


@pytest.mark.parametrize("fused", [False, True])
def test_flash_attention_bwd(chip, fused):
    from paddle_tpu.ops.pallas.flash_attn import _flash_attention_bwd_tpu
    q = chip(QKV, bf16)
    B, N, H, _ = QKV
    lse = chip((B, H, N, 128), f32)        # lane-broadcast logsumexp
    fn = functools.partial(_flash_attention_bwd_tpu, causal=True,
                           fused=fused)
    assert compiles(fn, q, q, q, q, lse, q) >= 1


def test_fused_ffn(chip, m=8192, h=2048, f=8192):
    from paddle_tpu.ops.pallas.fused_ffn import _fused_ffn_tpu, _pick_blocks
    blocks = _pick_blocks(m, h, f, 2)
    assert blocks is not None
    fn = functools.partial(_fused_ffn_tpu, block_m=blocks[0],
                           block_f=blocks[1], interpret=False)
    assert compiles(fn, chip((m, h), bf16), chip((h, f), bf16),
                    chip((f,), bf16), chip((f, h), bf16),
                    chip((h,), bf16)) == 1


def test_layer_norm(chip, rows=8192, h=2048):
    from paddle_tpu.ops.pallas import norms
    fn = functools.partial(
        norms._pallas_norm, functools.partial(norms._ln_kernel, eps=1e-5),
        bf16, interpret=False)
    assert compiles(fn, chip((rows, h), bf16), chip((h,), bf16),
                    chip((h,), bf16)) == 1


def test_kernel_names_and_scopes_reach_the_compiled_program(chip):
    """A ``pl.pallas_call``'s ``name=`` becomes the HLO instruction's
    name and, with the ``jax.named_scope``s around it, its ``op_name``:
    what a profiler trace of the chip shows for the kernel."""
    from paddle_tpu.ops.pallas.paged_attn import _paged_attention_tpu

    def layer(q, k, v, table, lens):
        with jax.named_scope("layer"), jax.named_scope("paged_attn"):
            return _paged_attention_tpu(q, k, v, table, lens)

    pool = chip((PAGES, 16, 32, 64), bf16)
    text = jax.jit(layer).lower(
        chip((SLOTS, 1, 32, 64), bf16), pool, pool, chip((SLOTS, MAXP), i32),
        chip((SLOTS,), i32)).compile().as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert call.lstrip().startswith("%paged_attn_decode")
    assert 'op_name="jit(layer)/layer/paged_attn/paged_attn_decode' in call
