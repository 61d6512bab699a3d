"""Table 3 reproduction (§5.2): statistical text analytics throughput —
feature extraction, Viterbi, Gibbs, Metropolis-Hastings, q-gram matching."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import Table
from repro.core.aggregates import run_local
from repro.methods.crf import (crf_init_params, extract_features,
                               gibbs_sample, mh_sample, viterbi_decode)
from repro.methods.string_match import (TrigramIndexAggregate, approx_match,
                                        encode_strings, jaccard_scores,
                                        trigram_signature)


def _timeit(fn, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def run(B: int = 256, T: int = 64, L: int = 5, F: int = 512):
    key = jax.random.PRNGKey(0)
    results = []
    toks = jax.random.randint(key, (B, T), 0, 5000)
    mask = jnp.ones((B, T), jnp.float32)

    dt = _timeit(jax.jit(lambda: extract_features(toks, F)))
    results.append(("text_feature_extraction", dt * 1e6,
                    f"tok_per_s={B * T / dt:.3g}"))

    params = crf_init_params(F, L, key, scale=0.3)
    feats = extract_features(toks, F)
    dt = _timeit(jax.jit(lambda: viterbi_decode(params, feats, mask)))
    results.append(("viterbi_inference", dt * 1e6,
                    f"tok_per_s={B * T / dt:.3g}"))

    dt = _timeit(lambda: gibbs_sample(params, feats, mask, key,
                                      n_sweeps=10)[0])
    results.append(("mcmc_gibbs_10sweeps", dt * 1e6,
                    f"site_updates_per_s={10 * B * T / dt:.3g}"))

    dt = _timeit(lambda: mh_sample(params, feats, mask, key,
                                   n_steps=100)[0])
    results.append(("mcmc_mh_100steps", dt * 1e6, ""))

    corpus = [f"entity number {i} the quick brown fox" for i in range(2000)]
    chars = encode_strings(corpus)
    tbl = Table.from_columns({"chars": chars,
                              "doc_id": jnp.arange(len(corpus))})
    t0 = time.perf_counter()
    index = run_local(TrigramIndexAggregate(len(corpus), 512), tbl)
    jax.block_until_ready(index)
    dt_index = time.perf_counter() - t0
    results.append(("trigram_index_build", dt_index * 1e6,
                    f"docs_per_s={len(corpus) / dt_index:.3g}"))

    q = trigram_signature(encode_strings(["entity number 42"]), 512)[0]
    dt = _timeit(jax.jit(lambda: jaccard_scores(index, q)))
    results.append(("approx_string_match", dt * 1e6,
                    f"docs_per_s={len(corpus) / dt:.3g}"))
    return results


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    for name, us, extra in run():
        print(f"{name},{us:.1f},{extra}")
