"""Batched serving example: mixed-length request queue through both
schedulers — continuous batching (slot-swap, the default) and the
bucketed reference — with identical sampled outputs (docs/serving.md).

    PYTHONPATH=src python examples/serve_lm.py
"""
import numpy as np
import jax

from repro.configs import ARCHS, reduced
from repro.models import init_params
from repro.compile_cache import enable_compile_cache
from repro.serve import ServingEngine, EngineConfig


def serve(cfg, params, lens, continuous):
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=4, max_seq=128, temperature=0.7, seed=7,
        continuous_batching=continuous,
    ))
    rng = np.random.default_rng(0)
    for uid, L in enumerate(lens):
        eng.submit(uid, rng.integers(0, cfg.vocab, L), max_new=12)
    return eng.run(), eng.last_stats


def main():
    enable_compile_cache()
    cfg = reduced(ARCHS["mistral-nemo-12b"])   # GQA family, tiny dims
    params = init_params(cfg, jax.random.PRNGKey(0))
    lens = [8, 8, 12, 12, 12, 16, 8, 16]

    out, st = serve(cfg, params, lens, continuous=True)
    for uid in sorted(out):
        print(f"req {uid} (prompt {lens[uid]} toks) -> "
              f"{np.asarray(out[uid]).tolist()}")
    assert len(out) == len(lens)
    idle = (1 - st["active_slot_steps"] / st["slot_steps"]
            if st["slot_steps"] else 0.0)
    print(f"\ncontinuous: {st['swaps']} slot swaps, "
          f"{st['n_tokens']} tokens, slot idle frac {idle:.3f}")

    # the bucketed reference serves the same queue with the same keys —
    # sampling is fold_in(seed, uid, position), not schedule-dependent
    ref, st_b = serve(cfg, params, lens, continuous=False)
    same = all(list(ref[u]) == list(out[u]) for u in out)
    idle_b = (1 - st_b["active_slot_steps"] / st_b["slot_steps"]
              if st_b["slot_steps"] else 0.0)
    print(f"bucketed reference: {len(set(lens))} buckets, "
          f"slot idle frac {idle_b:.3f}, identical outputs: {same}")
    assert same


if __name__ == "__main__":
    main()
