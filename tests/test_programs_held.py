"""The served families' programs, held to what they were before the
grouped-query family came in: its query groups in
``ops/pallas/paged_attn.py`` and ``ops/pallas/flash_prefill.py``, its
averaged shared experts and bias-free router in
``models/deepseek_v3.py``'s expert layer.  At a query group of 1, with
summed shared experts and a selection bias, every program must lower to
the SAME text as before, so that no cell the benchmark measures can move
for them.

Two readings, each a sha256 of text that holds no path or line number:

* the engine's own decode and prefill programs of GPT, ``ouro``, the
  latent family (kanana2's), ``longcat_flash`` and ``phi4flash`` (its
  rings) at the CPU tests' sizes, lowered to StableHLO on the CPU (the
  kernels' lax paths);
* the Pallas kernels' jaxprs at the widths the chip runs — the paged
  decode kernel over a bf16 and an int8 pool, the prefill forward with
  and without its shared rope key — where the kernel's body is traced
  in full (a lowered Mosaic body carries source locations; a jaxpr
  does not).

The digests were read on the tree before the change (same jax and
libtpu).  A change that is MEANT to alter one of these programs updates
its digest here and says why."""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
SDS = jax.ShapeDtypeStruct

ENGINE_PROGRAMS = {
    ("gpt", "decode"): "5c7aeacebecde7b6",
    ("gpt", "prefill"): "519d9f0e877203e8",
    ("ouro", "decode"): "136ff2087cb55220",
    ("ouro", "prefill"): "6f038466104f8f2b",
    ("kanana2", "decode"): "eb5916692e76cfb2",
    ("kanana2", "prefill"): "a0607c0ce170287a",
    ("longcat", "decode"): "818c16096952b231",
    ("longcat", "prefill"): "1a24826d5f346e1f",
    ("phi4flash", "decode"): "176245507ed01eb4",
    ("phi4flash", "prefill"): "a9777275438e65bb",
}
KERNEL_JAXPRS = {
    "paged_attn_32x64": "691340410ccce8b7",
    "paged_attn_16x128": "bcba22d1481fef9b",
    "paged_attn_int8_32x64": "ea7864f5b5972085",
    "flash_prefill_rope": "e526a40bf89ddda0",
    "flash_prefill": "ca6c413bd5b06369",
}


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def family_model(name):
    from paddle_tpu.models import deepseek_v3, gpt, longcat_flash, ouro, \
        phi4flash
    cfg = {"gpt": gpt.gpt_tiny, "ouro": ouro.ouro_tiny,
           "kanana2": deepseek_v3.deepseek_v3_tiny,
           "longcat": longcat_flash.longcat_flash_tiny,
           "phi4flash": phi4flash.phi4flash_tiny}[name]()
    module = {"gpt": gpt, "ouro": ouro, "kanana2": deepseek_v3,
              "longcat": longcat_flash, "phi4flash": phi4flash}[name]
    return module.init_params(cfg, jax.random.PRNGKey(0)), cfg


@pytest.mark.parametrize("family,program", sorted(ENGINE_PROGRAMS))
def test_engine_programs_lower_to_the_text_they_did(family, program,
                                                    monkeypatch):
    from paddle_tpu.inference.serving import PagedServingEngine
    monkeypatch.setenv("PADDLE_TPU_SERVING_DONATE", "1")
    eng = PagedServingEngine(family_model(family), slots=3, max_len=64,
                             page_size=8, num_pages=25, seq_buckets=(16, 32),
                             batch_buckets=(1, 2), capture_logits=False)
    ops = eng._cache_operands()
    z = functools.partial(np.zeros, dtype=np.int32)
    if program == "decode":
        low = eng._build_decode().lower(eng.params, *ops, z((3, 8)),
                                        *[z((3,))] * 4)
    else:
        low = eng._build_prefill(2, 32).lower(
            eng.params, *ops, z((2, 32)), np.ones((2,), np.int32),
            z((2, 4)), z((3,)), z((2,)))
    assert digest(low.as_text()) == ENGINE_PROGRAMS[family, program]


def kernel_jaxpr(name):
    from paddle_tpu.ops.pallas import flash_prefill, paged_attn
    if name.startswith("paged_attn"):
        nh, hd = map(int, name.rsplit("_", 1)[1].split("x"))
        dtype = i8 if "int8" in name else bf16
        pool = SDS((24, 1664, 16, nh * hd), dtype)
        scales = ((SDS((1664, 16, nh), f32),) * 2 if dtype == i8 else ())
        fn = paged_attn._paged_call
        args = (SDS((32, 1, nh, hd), bf16), (pool, pool), scales,
                SDS((32, 128), i32), SDS((32,), i32), SDS((), i32))
        return str(jax.make_jaxpr(fn)(*args))
    q = SDS((1, 1024, 4096), bf16)
    args = (q, q, SDS((1, 4096, 1024), bf16), SDS((1,), i32))
    if name.endswith("rope"):
        args += (q, SDS((1, 1024, 128), bf16))

    def fn(q, k, v_t, lens, *rope):
        return flash_prefill._flash_prefill_tpu(
            q, k, v_t, lens, heads=32, scale=0.1,
            **dict(zip(("q_rope", "k_rope"), rope)))
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("name", sorted(KERNEL_JAXPRS))
def test_kernels_at_one_query_a_head_trace_as_they_did(name):
    text = kernel_jaxpr(name)
    assert ".py" not in text        # no source location in the reading
    assert digest(text) == KERNEL_JAXPRS[name]
