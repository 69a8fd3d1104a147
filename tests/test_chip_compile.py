"""The kernels of the main paths, and the serving engine's whole
decode and prefill programs, compiled for the chip without the chip: the
installed TPU compiler takes a DESCRIBED ``v5e:2x2`` device, so each test
AOT-compiles one Pallas kernel at the widths ``chip_smoke.py`` serves and
trains, or one serving program at the benchmark's sizes (the
``on-chip-measurement`` guide, section 2, rehearsal 3).  Interpret mode
cannot see what Mosaic refuses — the paged decode kernels passed every
interpret test while the chip's compiler rejected their
``dot_dimension_numbers`` — so these are the tests that guard a kernel
between chip runs.  A compile that passes is not a chip run: it says
nothing about results or times.

The whole programs are read for what they do to the paged KV pool: they
must take it in the layout the device stores it in, update it in place,
and copy nothing of a layer's size (ISSUE 27).

One to three seconds each.  Skipped where the topology cannot be
described (no libtpu).  The persistent compilation cache is turned off
around them: a TPU executable written to it here cannot be read back
without a chip, and the next run would warn and compile again."""
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


# the benchmark's own serving shape, at the head split of gpt3_1p3b()
# (32 x 64) and of the 1.3B flagship's attention (16 x 128)
BENCH_PAGES, BENCH_SLOTS, BENCH_LAYERS, MAX_LEN = 1664, 32, 24, 2048
HEADS = [(32, 64), (16, 128)]


def paged_args(chip, nh, hd, ps, dtype, slots=BENCH_SLOTS,
               layers=BENCH_LAYERS, pages=BENCH_PAGES, max_len=MAX_LEN):
    """(q, (k_pool, v_pool)), (page_table, lens, layer) of the kernel
    (``_paged_call``'s operands but the int8 pool's scales)."""
    pool = chip((layers, pages, ps, nh * hd), dtype)
    return ((chip((slots, 1, nh, hd), bf16), (pool, pool)),
            (chip((slots, max_len // ps), i32), chip((slots,), i32),
             chip((), i32)))


@pytest.mark.parametrize("nh,hd", HEADS)
@pytest.mark.parametrize("ps", [16, 32])
def test_paged_attention_fp(chip, nh, hd, ps):
    from paddle_tpu.ops.pallas.paged_attn import _paged_call
    qkv, rest = paged_args(chip, nh, hd, ps, bf16)
    assert compiles(_paged_call, *qkv, (), *rest) == 1


@pytest.mark.parametrize("nh,hd", HEADS)
def test_paged_attention_int8(chip, nh, hd, ps=32):
    from paddle_tpu.ops.pallas.paged_attn import _paged_call
    qkv, rest = paged_args(chip, nh, hd, ps, i8)
    scale = chip((BENCH_PAGES, ps, nh), f32)
    assert compiles(_paged_call, *qkv, (scale, scale), *rest) == 1


def test_paged_attention_one_tp_shard(chip):
    """What one rank of a tp=4 engine runs: 8 of the 32 heads."""
    from paddle_tpu.ops.pallas.paged_attn import _paged_call
    qkv, rest = paged_args(chip, 8, 64, 16, bf16)
    assert compiles(_paged_call, *qkv, (), *rest) == 1


@pytest.mark.parametrize("dtype,group", [(f32, 1), (bf16, 2)],
                         ids=["float32-1-page", "bf16-2-pages"])
def test_paged_attention_largest_admitted_step(chip, dtype, group):
    """The corners of the VMEM bound that ``_use_pallas_paged`` and
    ``group_pages`` share, 32 heads x 256 at 64 positions a page: a
    float32 pool one page a step (the widest working copies), a bf16
    pool two pages a step — the rule stops there though 256 rows would
    allow four."""
    from paddle_tpu.ops.pallas import paged_attn
    qkv, rest = paged_args(chip, 32, 256, 64, dtype, slots=8, layers=4,
                           pages=128, max_len=4096)
    itemsize = jnp.dtype(dtype).itemsize
    assert paged_attn.group_pages(64, 64, 32 * 256, itemsize, 32) == group
    assert paged_attn._step_vmem_bytes(
        group, 64, 32 * 256, itemsize, 32) == paged_attn._MAX_STEP_VMEM_BYTES
    assert compiles(paged_attn._paged_call, *qkv, (), *rest) == 1


def test_paged_attention_gate_follows_the_compiler(chip, monkeypatch):
    """``_use_pallas_paged`` admits what compiles at one page a step (8
    bf16 rows of 2 x 64: half a packed tile, one 128-lane row) and
    turns away the merged axis Mosaic refuses to copy out of HBM (2
    heads x 16: "must be aligned to tiling (128)")."""
    from paddle_tpu.ops.pallas import paged_attn, utils as pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    small = dict(slots=4, layers=2, pages=64)
    qkv, rest = paged_args(chip, 2, 16, 8, bf16, **small)
    assert not paged_attn._use_pallas_paged(qkv[1][0], 2)
    with pytest.raises(Exception, match="aligned to tiling"):
        compiles(paged_attn._paged_call, *qkv, (), *rest)
    qkv, rest = paged_args(chip, 2, 64, 8, bf16, **small)
    assert paged_attn._use_pallas_paged(qkv[1][0], 2)
    assert paged_attn.group_pages(MAX_LEN // 8, 8, 128, 2, 2) == 1
    assert compiles(paged_attn._paged_call, *qkv, (), *rest) == 1


# --------------------------------------------------------------------------
# the whole serving programs at the benchmark's sizes: the pool is held in
# place (ISSUE 27).  Each case builds the engine as a user does, with shapes
# in place of weights and a two-page stand-in pool, and compiles the
# engine's OWN decode / prefill builders against the real pool's shape.
# --------------------------------------------------------------------------

POOLS = [pytest.param(32, 64, 16, None, id="bf16-32x64"),
         pytest.param(16, 128, 16, None, id="bf16-16x128"),
         pytest.param(32, 64, 32, "int8", id="int8-32x64")]
PROGRAMS = ["decode", "prefill_1x512", "prefill_4x1024"]


@pytest.fixture
def served(chip, monkeypatch):
    """``served(nh, hd, ps, kv_dtype) -> (engine, params, pools)``: a
    ``PagedServingEngine`` over the 1.3B widths with the chip's Pallas
    branch and buffer donation steered on here, in the test (what
    ``jax.devices()`` says is the CPU), and the pool operands' shapes at
    the benchmark's page count."""
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import gpt
    from paddle_tpu.ops.pallas import utils as pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_SERVING_DONATE", "1")

    def build(nh, hd, ps, kv_dtype):
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=nh * hd,
                            num_layers=BENCH_LAYERS, num_heads=nh,
                            ffn_size=8192, max_seq_len=MAX_LEN,
                            dtype="bfloat16", param_dtype="bfloat16")
        params = jax.tree_util.tree_map(
            lambda x: chip(x.shape, x.dtype),
            jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                           jax.random.PRNGKey(0)))
        eng = PagedServingEngine(
            (params, cfg), slots=BENCH_SLOTS, max_len=MAX_LEN, page_size=ps,
            num_pages=2, kv_dtype=kv_dtype, seq_buckets=(128, 512, 1024),
            batch_buckets=(1, 4), capture_logits=False)
        pools = tuple(
            chip((a.shape[0], BENCH_PAGES) + a.shape[2:], a.dtype)
            for a in eng._cache_operands())
        return eng, params, pools

    return build


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("nh,hd,ps,kv_dtype", POOLS)
def test_serving_programs_hold_the_pool_in_place(chip, served, nh, hd, ps,
                                                 kv_dtype, program):
    from paddle_tpu.inference.serving import pool_relayouts
    eng, params, pools = served(nh, hd, ps, kv_dtype)
    if program == "decode":
        fn = eng._build_decode()
        args = (chip((BENCH_SLOTS, MAX_LEN // ps), i32),
                *[chip((BENCH_SLOTS,), i32)] * 4)
    else:
        b, s = map(int, program.split("_")[1].split("x"))
        fn = eng._build_prefill(b, s)
        # ... the token vector and the wave's slots: the chain's operands
        args = (chip((b, s), i32), chip((b,), i32), chip((b, s // ps), i32),
                chip((BENCH_SLOTS,), i32), chip((b,), i32))
    compiled = fn.lower(params, *pools, *args).compile()
    assert pool_relayouts(compiled.as_text(), pools) == []
    mem = compiled.memory_analysis()
    # every pool operand comes back donated, in place
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    if program == "decode":
        assert compiled.as_text().count("tpu_custom_call") == 1
        assert mem.temp_size_in_bytes < (1 << 30)


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
    from paddle_tpu.ops.pallas.paged_attn import _paged_call

    def layer(*args):
        with jax.named_scope("layer"), jax.named_scope("paged_attn"):
            return _paged_call(*args)

    qkv, rest = paged_args(chip, 32, 64, 16, bf16)
    text = jax.jit(layer).lower(*qkv, (), *rest).compile().as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert call.lstrip().startswith("%paged_attn_decode")
    assert 'op_name="jit(layer)/layer/paged_attn/paged_attn_decode' in call


# --------------------------------------------------------------------------
# the deepseek_v3 family at kanana-2-30b-a3b's published widths, cut in
# depth as benchmark/configs/kanana2-30b-a3b-serve.json is (ISSUE 28)
# --------------------------------------------------------------------------

KANANA_SLOTS, KANANA_PS, KANANA_PAGES, KANANA_LAYERS = 64, 64, 1017, 8
# the sizing rule the configuration states: both programs, weights and
# pool included, under 90% of the chip's 15.75 GiB bytes_limit
KANANA_BUDGET = int(16_909_336_064 * 0.9)


@pytest.mark.parametrize("maxp", [MAX_LEN // KANANA_PS, 6],
                         ids=["table-32", "table-6"])
def test_paged_mla_decode_kernel(chip, maxp):
    """The latent kernel alone at 64 slots x pages of 64: 32 heads
    against one shared 512 + 64 wide key, the pool of 640-wide rows
    handed over whole and copied by the kernel a page at a time, a
    whole group waited for at once; at the cell's table (32 wide) and
    at a narrow one (6: two pages a step)."""
    from paddle_tpu.ops.pallas.paged_mla import _paged_mla_tpu
    fn = functools.partial(_paged_mla_tpu, scale=192 ** -0.5)
    text = jax.jit(fn).lower(
        chip((KANANA_SLOTS, 32, 512), bf16), chip((KANANA_SLOTS, 32, 64), bf16),
        chip((KANANA_LAYERS, KANANA_PAGES, KANANA_PS, 512 + 128), bf16),
        chip((KANANA_SLOTS, maxp), i32), chip((KANANA_SLOTS,), i32),
        chip((), i32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_mla_decode" in text


@pytest.mark.parametrize("rows", [384, 24576],
                         ids=["decode-64x6", "prefill-4x1024x6"])
@pytest.mark.parametrize("form", ["gate_up", "down"])
def test_grouped_matmul_kernel(chip, rows, form):
    """The experts' grouped matmul at 128 experts of [2048, 768] /
    [768, 2048] in a stack of 7 layers: a whole expert a block (gate and
    up together 12 MiB twice-buffered, over the default scoped VMEM, so
    the call states its limit), windows of 128 rows."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    k, n = (2048, 768) if form == "gate_up" else (768, 2048)
    stacks = (chip((KANANA_LAYERS - 1, 128, k, n), bf16),) * (
        2 if form == "gate_up" else 1)
    assert gm.window_rows(rows, 2) == 128
    assert gm.column_tile(128, k, n, 2, len(stacks),
                          2 if form == "gate_up" else 4) == n
    fn = functools.partial(gm._grouped_tpu, gate_up=form == "gate_up")
    text = jax.jit(fn).lower(chip((rows, k), bf16), chip((128,), i32),
                             stacks, chip((), i32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "%moe_grouped_matmul" + ("_gate_up" if form == "gate_up"
                                    else "") in text


@pytest.mark.parametrize("b,s", [(1, 1024), (4, 512), (4, 1024)])
def test_flash_prefill_kernel(chip, b, s):
    """The prefill flash forward alone at the buckets the gate gives
    it: 32 heads side by side along the lanes, q/k 128 + a 128-lane rope
    row, v 128 and transposed, the rope key one row a position for every
    head."""
    from paddle_tpu.ops.pallas import flash_prefill as fp
    assert 4 * b * 32 * s * s >= fp.MIN_SCORE_BYTES
    wide, row = chip((b, s, 32 * 128), bf16), chip((b, s, 128), bf16)
    text = jax.jit(lambda q, qr, k, kr, v_t, lens: fp._flash_prefill_tpu(
        q, k, v_t, lens, heads=32, scale=192 ** -0.5, q_rope=qr,
        k_rope=kr)).lower(wide, wide, wide, row, chip((b, 32 * 128, s), bf16),
                          chip((b,), i32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "flash_prefill_fwd" in text


@pytest.fixture
def served_kanana(chip, monkeypatch):
    """(engine, params, pools) of the benchmark's own configuration,
    shapes for weights."""
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import deepseek_v3
    from paddle_tpu.ops.pallas import utils as pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_SERVING_DONATE", "1")
    cfg = deepseek_v3.DeepseekV3Config(
        num_hidden_layers=KANANA_LAYERS, max_position_embeddings=MAX_LEN)
    params = jax.tree_util.tree_map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda k: deepseek_v3.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    eng = PagedServingEngine(
        (params, cfg), slots=KANANA_SLOTS, max_len=MAX_LEN,
        page_size=KANANA_PS, num_pages=2, seq_buckets=(128, 512, 1024),
        batch_buckets=(1, 4), capture_logits=False)
    pools = tuple(chip(s, bf16) for s in deepseek_v3.paged_pool_shapes(
        cfg, KANANA_PAGES, KANANA_PS))
    return eng, params, pools


@pytest.mark.parametrize("program", ["decode", "prefill_4x1024"])
def test_kanana_serving_programs_fit_and_hold_the_pool_in_place(
        chip, served_kanana, program):
    from paddle_tpu.inference.serving import pool_relayouts
    eng, params, pools = served_kanana
    if program == "decode":
        fn = eng._build_decode()
        args = (chip((KANANA_SLOTS, MAX_LEN // KANANA_PS), i32),
                *[chip((KANANA_SLOTS,), i32)] * 4)
    else:
        fn = eng._build_prefill(4, 1024)
        args = (chip((4, 1024), i32), chip((4,), i32),
                chip((4, 1024 // KANANA_PS), i32),
                chip((KANANA_SLOTS,), i32), chip((4,), i32))
    compiled = fn.lower(params, *pools, *args).compile()
    text = compiled.as_text()
    assert pool_relayouts(text, pools) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= KANANA_BUDGET, total / 2 ** 30
    if program == "decode":
        # layer 0 and the scan's body: the latent kernel twice, and no
        # expert stack sliced out for the grouped matmul (1.2 GB a layer
        # of temporaries when it was)
        assert text.count("paged_mla_decode") >= 2
        assert mem.temp_size_in_bytes < (64 << 20)
    else:
        # layer 0 and the scan's body: the flash forward twice, and the
        # float32 scores XLA's attention wrote (1.03 GiB of temporaries
        # at 4 x 1024 with them, 0.48 without; AOT, PR 38) nowhere
        assert text.count("flash_prefill_fwd") >= 2
        assert "f32[4,32,1024,1024]" not in text
        assert mem.temp_size_in_bytes < (640 << 20)
    # the experts' three products: our kernel twice in the scan's body
    # (gate-up, down) and the compiler's ragged-dot nowhere
    assert "%moe_grouped_matmul_gate_up" in text
    assert text.count("moe_grouped_matmul/pallas_call") >= 1
    assert "ragged" not in text
    for scope in ("mla_q", "mla_latent", "mla_attn", "mla_out",
                  "moe_router", "moe_held", "moe_shared", "dense_mlp"):
        assert scope in text, scope


# --------------------------------------------------------------------------
# the looped family (models/ouro.py) at the benchmark cell's sizes
# (benchmark/configs/ouro-2.6b-serve.json): 48 layers x 4 passes over a
# pool of 192 virtual layers, sized to the chip by the 90% rule
# --------------------------------------------------------------------------

@pytest.fixture
def served_ouro(chip, monkeypatch):
    import json
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import ouro
    from paddle_tpu.ops.pallas import utils as pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_SERVING_DONATE", "1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ouro-2.6b-serve.json")) as f:
        arch = json.load(f)
    fields = ouro.OuroConfig.__dataclass_fields__
    cfg = ouro.OuroConfig(**{k: v for k, v in arch.items() if k in fields})
    params = jax.tree_util.tree_map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda k: ouro.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    e = arch["engine"]
    eng = PagedServingEngine(
        (params, cfg), capture_logits=False,
        **dict(e, num_pages=2, seq_buckets=tuple(e["seq_buckets"]),
               batch_buckets=tuple(e["batch_buckets"])))
    pools = tuple(chip(s, bf16) for s in ouro.paged_pool_shapes(
        cfg, e["num_pages"], e["page_size"]))
    return eng, params, pools, e


@pytest.mark.parametrize("program", ["decode", "prefill_4x512"])
def test_ouro_programs_fit_the_rule_and_hold_the_pool_in_place(
        chip, served_ouro, program):
    from paddle_tpu.inference.serving import pool_relayouts
    eng, params, pools, e = served_ouro
    slots, ps = e["slots"], e["page_size"]
    assert pools[0].shape == (192, e["num_pages"], 16, 2048)
    if program == "decode":
        fn = eng._build_decode()
        args = (chip((slots, e["max_len"] // ps), i32),
                *[chip((slots,), i32)] * 4)
    else:
        fn = eng._build_prefill(4, 512)
        args = (chip((4, 512), i32), chip((4,), i32),
                chip((4, 512 // ps), i32), chip((slots,), i32),
                chip((4,), i32))
    compiled = fn.lower(params, *pools, *args).compile()
    text = compiled.as_text()
    assert pool_relayouts(text, pools) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # the MOST pages the rule allows: this many fit, one more would not
    assert total <= KANANA_BUDGET < total + 2 * pools[0].size // e[
        "num_pages"] * 2, total / 2 ** 30
    # no stack of weights copied into another layout (Wq and Wk were,
    # 0.75 GiB, while they were stored (in, out))
    assert mem.temp_size_in_bytes < (64 << 20)
    if program == "decode":
        # one kernel in the scans' body, whatever the passes and layers
        assert text.count("tpu_custom_call") == 1
        assert "paged_attn_decode" in text
    for scope in ("ut_step", "attn_qkv", "rope", "attn_out", "mlp",
                  "loop_norm", "exit_gate",
                  "kv_write" if program == "decode" else "kv_scatter"):
        assert scope in text, scope


# --------------------------------------------------------------------------
# the hybrid family (models/phi4flash.py) at the benchmark cell's sizes
# (benchmark/configs/phi4-mini-flash-serve.json): a one-layer page pool
# beside the slots' own rings, states and convolution tails, slots and
# pages by the sizing rule
# --------------------------------------------------------------------------

def test_paged_diff_attention_kernel(chip):
    """The differential decode kernel alone, over the pool (a table of
    64 pages a slot) and over the rings (a fixed table of 8)."""
    from paddle_tpu.ops.pallas import paged_diff_attn as pda
    slots = 176
    for layers, pages, width in ((1, 4562, 64), (8, slots * 8, 8)):
        pool = chip((layers, pages, 64, 1280), bf16)
        assert compiles(
            lambda q, k, v, table, lens, layer, lam: pda._paged_diff_call(
                q, (k, v), table, lens, layer, lam),
            chip((slots, 40, 64), bf16), pool, pool,
            chip((slots, width), i32), chip((slots,), i32), chip((), i32),
            chip((), f32)) == 1


@pytest.fixture
def served_phi4flash(chip, monkeypatch):
    import json
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import phi4flash
    from paddle_tpu.ops.pallas import utils as pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_SERVING_DONATE", "1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "phi4-mini-flash-serve.json")) as f:
        arch = json.load(f)
    fields = phi4flash.Phi4FlashConfig.__dataclass_fields__
    cfg = phi4flash.Phi4FlashConfig(
        **{k: v for k, v in arch.items() if k in fields})
    params = jax.tree_util.tree_map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda k: phi4flash.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    e = arch["engine"]
    # a small engine: its programs take their shapes from their operands
    eng = PagedServingEngine(
        (params, cfg), capture_logits=False,
        **dict(e, slots=8, num_pages=2, seq_buckets=tuple(e["seq_buckets"]),
               batch_buckets=tuple(e["batch_buckets"])))
    pools = (*(chip(s, bf16) for s in phi4flash.paged_pool_shapes(
        cfg, e["num_pages"], e["page_size"])),
        *(chip(s, d) for s, d in phi4flash.slot_state_shapes(
            cfg, e["slots"], e["page_size"])))
    return eng, params, pools, e


@pytest.mark.parametrize("program", ["decode", "prefill_4x1024"])
def test_phi4flash_programs_fit_the_rule_and_hold_the_state_in_place(
        chip, served_phi4flash, program):
    from paddle_tpu.inference.serving import pool_relayouts
    eng, params, pools, e = served_phi4flash
    slots, ps = e["slots"], e["page_size"]
    assert [p.shape for p in pools] == [
        (1, e["num_pages"], 64, 1280)] * 2 + [(8, slots * 8, 64, 1280)] * 2 \
        + [(9, slots, 5120, 16), (9, slots, 3, 5120)]
    if program == "decode":
        fn = eng._build_decode()
        args = (chip((slots, e["max_len"] // ps), i32),
                *[chip((slots,), i32)] * 4)
    else:
        fn = eng._build_prefill(4, 1024)
        args = (chip((4, 1024), i32), chip((4,), i32),
                chip((4, 1024 // ps), i32), chip((slots,), i32),
                chip((4,), i32))
    compiled = fn.lower(params, *pools, *args).compile()
    text = compiled.as_text()
    # the pool and the rings enter as they are stored and are not copied
    assert pool_relayouts(text, pools[:2]) == []
    assert pool_relayouts(text, pools[2:4]) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= KANANA_BUDGET, total / 2 ** 30
    if program == "decode":
        # no copy of a slot array, a ring or a stack of weights: the
        # float32 state of ONE layer is 55 MiB
        assert mem.temp_size_in_bytes < (64 << 20)
        # one kernel in each scan's body and one for the full layer
        assert text.count("tpu_custom_call") == 3
        assert "paged_diff_attn_decode" in text
    scopes = ["mlp", "window_attn", "full_attn", "cross_attn", "gmu",
              "kv_write", "head_sample"]
    scopes += (["ssm_step", "paged_diff_attn"] if program == "decode"
               else ["ssm_scan", "state_reset"])
    for scope in scopes:
        assert scope in text, scope


# --------------------------------------------------------------------------
# the expert-share family (models/longcat_flash.py) at the benchmark
# cell's sizes (benchmark/configs/longcat-flash-omni-serve.json): 4
# layers of two latent-attention blocks at 64 heads, 16 of 512 routed
# experts held beside 256 identity ones, slots and pages by the rule
# --------------------------------------------------------------------------

LONGCAT_ROWS = 200 * 12         # a decode step's assignments: slots x k


@pytest.mark.parametrize("rows", [LONGCAT_ROWS, 2 * 1024 * 12],
                         ids=["decode-200x12", "prefill-2x1024x12"])
@pytest.mark.parametrize("form", ["gate_up", "down"])
def test_grouped_matmul_kernel_at_the_held_experts(chip, rows, form):
    """16 held experts of [6144, 2048] / [2048, 6144] in a stack of 4
    layers, the sizes summing to a fraction of the rows: the zeroing
    steps and the column tiles VMEM asks for at these widths."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    k, n = (6144, 2048) if form == "gate_up" else (2048, 6144)
    stacks = (chip((4, 16, k, n), bf16),) * (2 if form == "gate_up" else 1)
    fn = functools.partial(gm._grouped_tpu, gate_up=form == "gate_up")
    text = jax.jit(fn).lower(chip((rows, k), bf16), chip((16,), i32),
                             stacks, chip((), i32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.fixture
def served_longcat(chip, monkeypatch):
    import json
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import longcat_flash as lc
    from paddle_tpu.ops.pallas import utils as pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_SERVING_DONATE", "1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "longcat-flash-omni-serve.json")) as f:
        arch = json.load(f)
    fields = lc.LongcatFlashConfig.__dataclass_fields__
    cfg = lc.LongcatFlashConfig(
        **{k: v for k, v in arch.items() if k in fields})
    params = jax.tree_util.tree_map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda k: lc.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    e = arch["engine"]
    eng = PagedServingEngine(
        (params, cfg), capture_logits=False,
        **dict(e, num_pages=2, seq_buckets=tuple(e["seq_buckets"]),
               batch_buckets=tuple(e["batch_buckets"])))
    pools = tuple(chip(s, bf16) for s in lc.paged_pool_shapes(
        cfg, e["num_pages"], e["page_size"]))
    return eng, params, pools, e


@pytest.mark.parametrize("program", ["decode", "prefill_2x1024"])
def test_longcat_programs_fit_the_rule_and_hold_the_pool_in_place(
        chip, served_longcat, program):
    from paddle_tpu.inference.serving import pool_relayouts
    eng, params, pools, e = served_longcat
    slots, ps = e["slots"], e["page_size"]
    assert [p.shape for p in pools] == [(8, e["num_pages"], 64, 512 + 128)]
    if program == "decode":
        fn = eng._build_decode()
        args = (chip((slots, e["max_len"] // ps), i32),
                *[chip((slots,), i32)] * 4)
    else:
        fn = eng._build_prefill(2, 1024)
        args = (chip((2, 1024), i32), chip((2,), i32),
                chip((2, 1024 // ps), i32), chip((slots,), i32),
                chip((2,), i32))
    compiled = fn.lower(params, *pools, *args).compile()
    text = compiled.as_text()
    assert pool_relayouts(text, pools) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= KANANA_BUDGET, total / 2 ** 30
    if program == "decode":
        # the scan's body: the latent kernel once a block, at 64 heads
        assert text.count("paged_mla_decode") >= 2
        assert mem.temp_size_in_bytes < (256 << 20)
    else:
        # the expert layer's [T x 12, 6144] rows held whole: the cap on
        # the buckets (batch 1 and 2) is what keeps this under the rule
        assert text.count("flash_prefill_fwd") >= 2
        assert mem.temp_size_in_bytes < (1536 << 20)
    assert "%moe_grouped_matmul_gate_up" in text
    assert "ragged" not in text
    for scope in ("mla_q_lora", "mla_latent", "mla_attn", "mla_out",
                  "scmoe_shortcut", "moe_router", "moe_held", "moe_zero",
                  "dense_mlp"):
        assert scope in text, scope


# --------------------------------------------------------------------------
# the grouped-query window/global family (models/cohere2_moe.py) at the
# benchmark cell's sizes (benchmark/configs/command-a-plus-serve.json): 4
# layers (three rope window layers and a NoPE full one), 128 query heads
# on 8 K/V heads of 128, rings of 4,096 rows, 16 of 128 experts held,
# slots and pages by the rule
# --------------------------------------------------------------------------

@pytest.mark.parametrize("table", [64, 128], ids=["ring", "pool"])
def test_grouped_query_decode_kernel_at_the_served_widths(chip, table):
    """16 query heads a K/V head, a ring's table of 64 pages and the
    pool's of 128 (max_len 8192): one kernel, no block-diagonal query."""
    from paddle_tpu.ops.pallas import paged_attn
    pool = chip((3, 64 * 64, 64, 1024), bf16)
    assert paged_attn.group_pages(table, 64, 1024, 2, 128) == 4
    assert compiles(paged_attn._paged_call, chip((64, 1, 128, 128), bf16),
                    (pool, pool), (), chip((64, table), i32),
                    chip((64,), i32), chip((), i32)) == 1


def test_prefill_forward_group_factor_at_the_served_widths(chip):
    from paddle_tpu.ops.pallas import flash_prefill
    fn = functools.partial(flash_prefill._flash_prefill_tpu, heads=128,
                           scale=128 ** -0.5, group=16)
    assert compiles(fn, chip((1, 4096, 128 * 128), bf16),
                    chip((1, 4096, 8 * 128), bf16),
                    chip((1, 8 * 128, 4096), bf16), chip((1,), i32)) == 1


@pytest.fixture
def served_cohere2(chip, monkeypatch):
    import json
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import cohere2_moe as cm
    from paddle_tpu.ops.pallas import utils as pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_SERVING_DONATE", "1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-serve.json")) as f:
        arch = json.load(f)
    fields = cm.Cohere2MoeConfig.__dataclass_fields__
    cfg = cm.Cohere2MoeConfig(**{k: v for k, v in arch.items()
                                 if k in fields})
    params = jax.tree_util.tree_map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda k: cm.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    e = arch["engine"]
    eng = PagedServingEngine(
        (params, cfg), capture_logits=False,
        **dict(e, slots=8, num_pages=2, seq_buckets=tuple(e["seq_buckets"]),
               batch_buckets=tuple(e["batch_buckets"])))
    pools = (*(chip(s, bf16) for s in cm.paged_pool_shapes(
        cfg, e["num_pages"], e["page_size"])),
        *(chip(s, d) for s, d in cm.slot_state_shapes(
            cfg, e["slots"], e["page_size"])))
    return eng, params, pools, e


@pytest.mark.parametrize("program", ["decode", "prefill_1x4096"])
def test_cohere2_programs_fit_the_rule_and_write_no_scores(
        chip, served_cohere2, program):
    """The decode program reads every layer through the grouped-query
    kernel; the largest prefill wave takes the flash forward in both
    kinds of layer and holds no [1, 128, 4096, 4096] float32 scores (8
    GiB); both fit the 90% rule at the sized slots and pages, the pool
    and the rings taken in place."""
    from paddle_tpu.inference.serving import pool_relayouts
    eng, params, pools, e = served_cohere2
    slots, ps = e["slots"], e["page_size"]
    assert [p.shape for p in pools] == [(1, e["num_pages"], 64, 1024)] * 2 \
        + [(3, slots * 64, 64, 1024)] * 2
    if program == "decode":
        fn = eng._build_decode()
        args = (chip((slots, e["max_len"] // ps), i32),
                *[chip((slots,), i32)] * 4)
    else:
        fn = eng._build_prefill(1, 4096)
        args = (chip((1, 4096), i32), chip((1,), i32),
                chip((1, 4096 // ps), i32), chip((slots,), i32),
                chip((1,), i32))
    compiled = fn.lower(params, *pools, *args).compile()
    text = compiled.as_text()
    assert pool_relayouts(text, pools[:2]) == []
    assert pool_relayouts(text, pools[2:]) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= KANANA_BUDGET, total / 2 ** 30
    assert "f32[1,128,4096,4096]" not in text
    if program == "decode":
        # one kernel in the window layers' scan body, one for the full
        # layer, and the experts' two grouped matmuls in each
        assert text.count("paged_attn_decode") >= 2
        assert mem.temp_size_in_bytes < (64 << 20)
    else:
        assert text.count("flash_prefill_fwd") >= 2
        assert mem.temp_size_in_bytes < (1 << 30)
    assert "%moe_grouped_matmul_gate_up" in text
    for scope in ("window_attn", "full_attn", "routed_share",
                  "shared_experts", "kv_write", "head_sample"):
        assert scope in text, scope
