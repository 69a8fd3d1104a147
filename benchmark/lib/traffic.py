"""The one general traffic generator.  A traffic mix is a data file
(``benchmark/traffic/<name>.json``) of laws and parameters; everything a
run sends is made here from ``--seed`` BEFORE the window opens, byte for
byte the same for the same seed.

Every seed gets the same WORK in another order: a law is sampled at its
own quantiles (the inverse CDF at (i + 0.5) / n), so the multiset of
lengths is fixed by the file, and the seed only permutes it.  Runs with
different seeds then differ by ordering alone, which is what lets twelve
runs with twelve seeds be read as one spread.

Length law (``{"law": "lognormal", median, sigma, min, max}``): clipped,
the heavy tail of real prompt and answer lengths.  The `benchmark` PR
that adds a cell needing more (arrival gaps, shared prefixes: PERF.md
section 7) brings the law with the cell and its chip runs.
Token ids: ``uniform`` over the vocabulary, or ``zipf`` (exponent, id 0
the most frequent) so that a language model has a unigram law to learn.
"""
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def stream_rng(seed, stream):
    """An independent generator per (seed, purpose): adding a stream never
    shifts another's draws.  Any whole ``seed`` >= 0 is accepted."""
    return np.random.default_rng([int(seed), int(stream)])


# stream ids: fixed, so the same seed always means the same bytes
S_PROMPT_LEN, S_OUTPUT_LEN, S_TOKENS, S_SAMPLE = range(4)


def law_quantiles(law, n):
    """``n`` values of ``law`` at its own quantiles, ascending."""
    u = (np.arange(n) + 0.5) / n
    if law["law"] != "lognormal":
        raise ValueError(f"unknown law {law['law']!r}")
    z = np.array([_NORMAL.inv_cdf(x) for x in u])
    v = np.exp(math.log(law["median"]) + law["sigma"] * z)
    return np.clip(v, law["min"], law["max"])


def stratified_stream(law, n, block, rng):
    """``n`` values of ``law`` in a seeded order, such that every run of
    ``block`` consecutive values holds one value from each of ``block``
    equal strata of the law: a prefix of ANY length is a balanced sample,
    so a run that consumes 300 requests and one that consumes 320 did the
    same kind of work.  ``block`` >= ``n`` is a plain permutation."""
    block = max(1, min(int(block), n))
    n_blocks = -(-n // block)
    q = law_quantiles(law, n_blocks * block).reshape(block, n_blocks)
    for row in q:                   # stratum j: which block gets which
        rng.shuffle(row)
    out = q.T.copy()                # [n_blocks, block]
    for blk in out:                 # order inside a block
        rng.shuffle(blk)
    return out.reshape(-1)[:n]


def token_ids(law, vocab_size, n, rng):
    kind = law["law"]
    if kind == "uniform":
        return rng.integers(0, vocab_size, size=n, dtype=np.int32)
    if kind == "zipf":
        return zipf_sampler(vocab_size, law["exponent"])(n, rng)
    raise ValueError(f"unknown token law {kind!r}")


def zipf_sampler(vocab_size, exponent):
    """``sample(n, rng)`` of ids with p(id) ~ 1 / (id + 1) ** exponent."""
    w = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0

    def sample(n, rng):
        return np.searchsorted(cdf, rng.random(n), side="right").astype(
            np.int32)
    return sample


def requests(traffic, vocab_size, n, seed):
    """``n`` requests as ``[(prompt int32 array, max_new_tokens)]``."""
    block = traffic.get("block", n)
    p_len = stratified_stream(traffic["prompt_len"], n, block,
                              stream_rng(seed, S_PROMPT_LEN))
    o_len = stratified_stream(traffic["output_len"], n, block,
                              stream_rng(seed, S_OUTPUT_LEN))
    tok_rng = stream_rng(seed, S_TOKENS)
    tok_law = traffic.get("token_ids", {"law": "uniform"})
    return [(token_ids(tok_law, vocab_size, int(round(p)), tok_rng),
             int(round(o))) for p, o in zip(p_len, o_len)]


def schedule_bytes(reqs):
    """One byte string of a whole schedule — what 'the same seed gives
    the same schedule byte for byte' is tested on."""
    parts = [np.asarray([m for _, m in reqs], np.int64).tobytes()]
    parts += [p.tobytes() for p, _ in reqs]
    return b"".join(parts)
