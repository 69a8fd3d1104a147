"""Tests for framework/compile_cache.py — the unified compile layer
(ISSUE 14): site keying/LRU/counters, donation-aware keys, cross-process
stable keys, the AOT artifact store round trip (fresh process, zero XLA
compiles, bitwise-identical decode output), and corrupt/stale artifact
rejection falling back to recompile."""
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.framework import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code, *argv, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("PADDLE_AOT_CACHE_DIR", "PADDLE_JIT_CACHE_DIR",
                 "JAX_COMPILATION_CACHE_DIR"):
        env.pop(name, None)
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, "-c", code, *map(str, argv)],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.fixture(autouse=True)
def _no_ambient_store(monkeypatch):
    monkeypatch.delenv("PADDLE_AOT_CACHE_DIR", raising=False)
    prev = cc.set_artifact_dir(None)
    yield
    cc.set_artifact_dir(prev)


# --------------------------------------------------------------------------
# keying + LRU + counters
# --------------------------------------------------------------------------

class TestSite:
    def test_get_builds_once_and_hits(self):
        s = cc.site("t.basic")
        built = []
        k = cc.make_key("a", (4,), donate=())
        f1 = s.get(k, lambda: built.append(1) or (lambda: 1))
        f2 = s.get(k, lambda: built.append(2) or (lambda: 2))
        assert f1 is f2 and built == [1]

    def test_donation_aware_keys_never_collide(self):
        # a donated and a non-donated executable of the same abstract
        # signature must be DISTINCT entries (calling the donated one
        # with live buffers consumes them)
        s = cc.site("t.donate")
        k_plain = cc.make_key("decode", (8, 16), donate=())
        k_donated = cc.make_key("decode", (8, 16), donate=(1, 2))
        assert k_plain != k_donated
        f1 = s.get(k_plain, lambda: ("plain",))
        f2 = s.get(k_donated, lambda: ("donated",))
        assert f1 != f2 and len(s) == 2

    def test_lru_eviction_and_counters(self):
        fam = cc.compile_stats()
        h0, b0, e0 = fam["hits"], fam["builds"], fam["evictions"]
        s = cc.site("t.lru", maxsize=2)
        for i in range(3):
            s.get(cc.make_key(i), lambda i=i: i)
        assert len(s) == 2
        assert s.get(cc.make_key(2), lambda: "rebuilt") == 2  # still in
        assert s.get(cc.make_key(0), lambda: "rebuilt") == "rebuilt"
        fam = cc.compile_stats()
        assert fam["builds"] - b0 == 4
        assert fam["hits"] - h0 == 1
        assert fam["evictions"] - e0 == 2
        # per-site breakdown rides the same family
        assert fam["t_lru_builds"] == 4

    def test_legacy_alias_adapter(self):
        events = []
        s = cc.site("t.legacy", maxsize=1, legacy_inc=events.append)
        s.get(cc.make_key(1), lambda: 1)
        s.get(cc.make_key(1), lambda: 1)
        s.get(cc.make_key(2), lambda: 2)       # evicts key 1
        assert events == ["build", "hit", "evict", "build"]

    def test_signature_lru_backcompat(self):
        # the PR-5 constructor shape still works (ops.dispatch re-export)
        from paddle_tpu.ops.dispatch import SignatureLRU

        class Stats:
            def __init__(self):
                self.d = {}

            def inc(self, k, v=1):
                self.d[k] = self.d.get(k, 0) + v
        st = Stats()
        lru = SignatureLRU(maxsize=4, stats=st, compile_key="compiles",
                           hit_key="hits")
        lru.get(("a",), lambda: 1)
        lru.get(("a",), lambda: 2)
        assert st.d == {"compiles": 1, "hits": 1}

    def test_unhashable_key_raises_typeerror(self):
        s = cc.site("t.unhash")
        with pytest.raises(TypeError):
            s.lookup(([1, 2],))

    def test_bucket_ladder_helpers(self):
        assert cc.pow2_ladder(16, 128) == (16, 32, 64, 128)
        assert cc.pow2_ladder(16, 100) == (16, 32, 64, 100)
        assert cc.next_pow2(0) == 1 and cc.next_pow2(65) == 128
        assert cc.pick_bucket(33, (16, 32, 64)) == 64
        with pytest.raises(ValueError):
            cc.pick_bucket(65, (16, 32, 64))

    def test_compile_family_in_fast_path_summary(self):
        from paddle_tpu import profiler
        fam = profiler.fast_path_summary()["compile"]
        for k in ("hits", "builds", "evictions", "aot_hits",
                  "aot_errors", "persistent_cache_misses", "count"):
            assert k in fam, k


# --------------------------------------------------------------------------
# cross-process key stability
# --------------------------------------------------------------------------

_KEY_PROBE = """
import sys
from paddle_tpu.models import gpt as G
from paddle_tpu.inference.serving import PagedServingEngine
import jax
cfg = G.gpt_tiny()
params = G.init_params(cfg, jax.random.PRNGKey(0))
eng = PagedServingEngine((params, cfg), slots=2, max_len=32,
                         seq_buckets=[16], batch_buckets=[1], page_size=8)
print(eng._aot_key("decode"))
print(eng._aot_key("prefill", b=1, s=16))
from paddle_tpu.framework import compile_cache as cc
print(cc.stable_hash(eng._aot_key("decode")))
"""


class TestStableKeys:
    @pytest.mark.slow
    def test_keys_identical_across_processes(self):
        a = _run_py(_KEY_PROBE)
        b = _run_py(_KEY_PROBE)
        assert a == b
        assert "serving/decode/" in a

    def test_stable_hash_deterministic(self):
        assert cc.stable_hash("x") == cc.stable_hash("x")
        assert cc.stable_hash("x") != cc.stable_hash("y")
        assert len(cc.stable_hash("x", 20)) == 40


# --------------------------------------------------------------------------
# AOT artifact store
# --------------------------------------------------------------------------

_BOOT = """
import json, os, sys
import numpy as np
from jax import monitoring
events = []
monitoring.register_event_duration_secs_listener(
    lambda e, d, **kw: events.append(e) if "backend_compile" in e
    else None)
from paddle_tpu.models import gpt as G
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.framework.compile_cache import compile_stats
mode, work = sys.argv[1], sys.argv[2]
cfg = G.gpt_tiny()
if mode == "seed":
    import jax
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    G.save_params_npz(os.path.join(work, "params.npz"), params)
else:
    params = G.load_params_npz(os.path.join(work, "params.npz"))
eng = PagedServingEngine((params, cfg), slots=2, max_len=32,
                         seq_buckets=[16], batch_buckets=[1],
                         page_size=8, capture_logits=True)
eng.warmup()
req = eng.submit(np.arange(1, 7, dtype=np.int32), 6)
while not req.done:
    eng.step()
cs = compile_stats()
print(json.dumps({
    "mode": mode, "compiles": len(events), "tokens": req.tokens,
    "logits_sha": __import__("hashlib").sha256(
        np.stack(req.logits).astype(np.float32).tobytes()).hexdigest(),
    "aot": {k: cs[k] for k in ("aot_hits", "aot_misses", "aot_saves",
                               "aot_errors", "aot_stale")},
    "decode_compiles": eng.stats()["decode_compiles"]}))
"""


class TestArtifactRoundTrip:
    def _seed(self, tmp_path):
        work = str(tmp_path)
        env = {"PADDLE_AOT_CACHE_DIR": os.path.join(work, "aot")}
        out = json.loads(_run_py(_BOOT, "seed", work, env_extra=env))
        assert out["aot"]["aot_saves"] >= 1
        arts = os.listdir(os.path.join(work, "aot"))
        assert arts and all(a.endswith(".aotx") for a in arts)
        return work, env, out

    def test_round_trip_zero_compiles_bitwise_output(self, tmp_path):
        work, env, seeded = self._seed(tmp_path)
        out = json.loads(_run_py(_BOOT, "load", work, env_extra=env))
        # a fresh process served entirely from artifacts: no traces, no
        # lowering, ZERO backend compiles — and its decode output is
        # BITWISE the seeding process's (same logits bytes, same tokens)
        assert out["compiles"] == 0
        assert out["aot"]["aot_hits"] >= 1
        assert out["aot"]["aot_errors"] == 0
        assert out["decode_compiles"] == 1
        assert out["tokens"] == seeded["tokens"]
        assert out["logits_sha"] == seeded["logits_sha"]

    def test_corrupt_artifact_falls_back_to_recompile(self, tmp_path):
        work, env, seeded = self._seed(tmp_path)
        aot = os.path.join(work, "aot")
        for name in os.listdir(aot):
            with open(os.path.join(aot, name), "wb") as f:
                f.write(b"not a pickle at all")
        out = json.loads(_run_py(_BOOT, "load", work, env_extra=env))
        # degraded, never crashed: everything recompiled, output intact
        assert out["compiles"] > 0
        assert out["aot"]["aot_hits"] == 0
        assert out["tokens"] == seeded["tokens"]
        assert out["logits_sha"] == seeded["logits_sha"]

    @pytest.mark.slow
    def test_stale_artifact_rejected(self, tmp_path):
        work, env, seeded = self._seed(tmp_path)
        aot = os.path.join(work, "aot")
        for name in os.listdir(aot):
            p = os.path.join(aot, name)
            with open(p, "rb") as f:
                rec = pickle.load(f)
            rec["jax"] = "0.0.0-stale"       # a different jax built it
            with open(p, "wb") as f:
                pickle.dump(rec, f)
        out = json.loads(_run_py(_BOOT, "load", work, env_extra=env))
        assert out["compiles"] > 0           # recompiled, not loaded
        assert out["aot"]["aot_hits"] == 0
        assert out["aot"]["aot_stale"] >= 1
        assert out["tokens"] == seeded["tokens"]

    def test_wrong_key_payload_rejected(self, tmp_path):
        # a digest-colliding / hand-renamed file whose embedded key
        # differs must be treated as stale, not served
        store = cc.ArtifactStore(str(tmp_path / "aot2"))
        import jax
        compiled = jax.jit(lambda x: x * 2).lower(
            jax.ShapeDtypeStruct((4,), np.float32)).compile()
        store.save("key-A", compiled)
        src = store._path("key-A")
        dst = store._path("key-B")
        os.rename(src, dst)
        fn, reason = store.load("key-B")
        assert fn is None and reason == "stale"
        # and the real key round-trips in-process
        store.save("key-C", compiled)
        fn, reason = store.load("key-C")
        assert reason is None
        got = np.asarray(fn(np.ones((4,), np.float32)))
        np.testing.assert_array_equal(got, 2 * np.ones((4,)))


class TestArtifactStoreUnits:
    def test_missing_dir_is_miss(self, tmp_path):
        store = cc.ArtifactStore(str(tmp_path / "nope"))
        fn, reason = store.load("whatever")
        assert fn is None and reason == "miss"

    def test_site_get_without_store_builds(self, tmp_path):
        # stable_key given but no store configured: plain build path
        s = cc.site("t.nostore")
        out = s.get(cc.make_key("k"), lambda: "built",
                    stable_key="t/nostore/k")
        assert out == "built"

    def test_artifact_ready_probe_validates(self, tmp_path):
        cc.set_artifact_dir(str(tmp_path))
        try:
            assert not cc.artifact_ready("no-such-key")
            import jax
            compiled = jax.jit(lambda x: x + 1).lower(
                jax.ShapeDtypeStruct((2,), np.float32)).compile()
            store = cc.ArtifactStore(str(tmp_path))
            store.save("k1", compiled)
            assert cc.artifact_ready("k1")
            # a merely-EXISTING but stale artifact must NOT be ready —
            # warmup would otherwise skip the compile wave and push the
            # compile into live traffic (review finding)
            with open(store._path("k1"), "rb") as f:
                rec = pickle.load(f)
            rec["jax"] = "0.0.0-stale"
            with open(store._path("k1"), "wb") as f:
                pickle.dump(rec, f)
            assert os.path.exists(store._path("k1"))
            assert not cc.artifact_ready("k1")
            # corrupt file: same answer, no crash
            with open(store._path("k1"), "wb") as f:
                f.write(b"garbage")
            assert not cc.artifact_ready("k1")
        finally:
            cc.set_artifact_dir(None)


# --------------------------------------------------------------------------
# where the persistent cache lives: one rule (jax_compat.resolve_cache_dir)
# --------------------------------------------------------------------------

_CACHE_RULE_PROBE = """
import json, sys
import jax
set_in_code = []
_update = jax.config.update
def spy(name, value):
    if name == "jax_compilation_cache_dir":
        set_in_code.append(value)
    return _update(name, value)
jax.config.update = spy
from paddle_tpu.framework import jax_compat
default = jax_compat.checkout_cache_dir() if sys.argv[1] == "entry" else None
got = jax_compat.enable_persistent_cache(default)
print(json.dumps({"returned": got, "set_in_code": set_in_code,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.fixture(scope="module")
def cache_rule(tmp_path_factory):
    """Every scenario of the rule, probed once each in its own fresh
    interpreter (the variables are read when jax is imported) — all at
    the same time, since tier-1's budget is tight."""
    from concurrent.futures import ThreadPoolExecutor
    tmp = tmp_path_factory.mktemp("cache_rule")
    x, y = str(tmp / "x"), str(tmp / "y")
    scenarios = {
        "jax_wins": ("entry", {"JAX_COMPILATION_CACHE_DIR": x,
                               "PADDLE_JIT_CACHE_DIR": y}),
        "paddle_second": ("entry", {"PADDLE_JIT_CACHE_DIR": y}),
        "entry_default": ("entry", {}),
        "entry_default_again": ("entry", {}),
        "library": ("library", {}),
        "import_only": (None, {}),
    }

    def probe(item):
        name, (caller, env) = item
        if caller is None:
            out = _run_py(
                "import paddle_tpu, paddle_tpu.inference.serving, "
                "paddle_tpu.inference.fleet\n"
                "import jax, json\n"
                "print(json.dumps(len(jax._src.xla_bridge._backends)))")
        else:
            out = _run_py(_CACHE_RULE_PROBE, caller, env_extra=env)
        return name, json.loads(out.strip().splitlines()[-1])

    with ThreadPoolExecutor(len(scenarios)) as pool:
        got = dict(pool.map(probe, scenarios.items()))
    return dict(got, x=x, y=y)


class TestCacheDirRule:
    def test_jax_variable_wins_and_nothing_is_set_in_code(self, cache_rule):
        """JAX_COMPILATION_CACHE_DIR places the cache from outside: jax
        read it at import, the program sets no directory — not the
        entry-point default and not PADDLE_JIT_CACHE_DIR either."""
        x = cache_rule["x"]
        assert cache_rule["jax_wins"] == {
            "returned": x, "set_in_code": [], "config": x}

    def test_paddle_variable_is_second(self, cache_rule):
        y = cache_rule["y"]
        assert cache_rule["paddle_second"] == {
            "returned": y, "set_in_code": [y], "config": y}

    def test_entry_point_default_is_the_fixed_checkout_path(self,
                                                            cache_rule):
        """No variable: entry-point scripts land in <checkout>/.jax_cache,
        the same path in every process (the path is part of the cache
        key — a temp name, pid or time in it would never hit)."""
        want = os.path.join(REPO, ".jax_cache")
        assert (cache_rule["entry_default"]
                == cache_rule["entry_default_again"]
                == {"returned": want, "set_in_code": [want],
                    "config": want})

    def test_library_constructors_start_no_cache(self, cache_rule):
        """No variable, no default: an engine built under the tests must
        keep its compile counts, so the library starts nothing."""
        assert cache_rule["library"] == {
            "returned": None, "set_in_code": [], "config": None}


def test_import_starts_no_backend(cache_rule):
    """Importing the package — serving engine and fleet router included —
    must not initialise a jax backend: a parent that has touched jax
    holds the chip, and a fleet's router process must stay off it."""
    assert cache_rule["import_only"] == 0
