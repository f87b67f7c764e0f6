#!/usr/bin/env python
"""Fused Pallas paged-decode kernel drill CLI: prove through the public
engine surface that

* greedy decode tokens are BIT-IDENTICAL between ``decode_kernel='pallas'``
  (interpret mode on CPU, native on TPU) and the XLA dense-gather twin in
  fp32 — across ragged sequence lengths, block-boundary prompts, an int8
  KV pool, and speculative verify rounds (the wide-decode shape),
* a demote→promote cycle through the FUSED promote-fence prologue (the
  promotions riding the decode dispatch instead of a standalone donated
  scatter) yields the same greedy tokens as the standalone-fence xla path,
  with ``tier_report()`` counting the saved dispatches.

Neither kernel is timed here: which is faster on the chip is ROADMAP.md D3's
open question, and a serving cell's to answer.

    python tools/decode_kernel_drill.py --list
    python tools/decode_kernel_drill.py --scenario parity
    python tools/decode_kernel_drill.py --scenario fused-fence
    python tools/decode_kernel_drill.py --all

Exit code 0 = invariants held; 1 = violated (details on stdout as JSON).
Slow pytest wrappers live in ``tests/unit/test_decode_kernel.py`` under
the ``pallas`` + ``slow`` markers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class DrillFailure(AssertionError):
    pass


def check(ok, msg, details):
    if not ok:
        raise DrillFailure(f"{msg}: {json.dumps(details, default=str)}")


def _fp32_pair(block_size=8, max_sequences=8, max_seq_len=None, **kw):
    """Two engines over the SAME fp32 tiny model/params, one per kernel."""
    import jax

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.presets import get_preset
    from deepspeed_tpu.models.transformer import TransformerLM

    cfg = get_preset("tiny", dtype="float32",
                     max_seq_len=max_seq_len or 64)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    engines = {
        kern: InferenceEngineV2(model, params=params,
                                max_sequences=max_sequences,
                                block_size=block_size, decode_kernel=kern,
                                **kw)
        for kern in ("pallas", "xla")}
    return cfg, engines


def scenario_parity() -> dict:
    """fp32 greedy-token identity pallas vs xla: ragged lengths,
    block-boundary prompts, int8 KV, and spec-verify rounds."""
    import numpy as np

    detail = {}
    # ragged lengths incl. exact block-boundary prompts (block_size=8)
    cfg, engines = _fp32_pair(block_size=8)
    rng = np.random.default_rng(3)
    lens = [3, 8, 11, 16, 21]                 # 8 and 16 sit on boundaries
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    toks = {}
    for kern, eng in engines.items():
        uids = list(range(len(prompts)))
        first = eng.put(uids, prompts)
        starts = [int(np.argmax(first[u])) for u in uids]
        out = eng.decode_batch(uids, starts, steps=6)
        toks[kern] = np.stack([out[u] for u in uids])
        assert eng.decode_kernel == kern, eng.decode_kernel
    check(np.array_equal(toks["pallas"], toks["xla"]),
          "ragged greedy tokens diverged",
          {"pallas": toks["pallas"].tolist(), "xla": toks["xla"].tolist()})
    detail["ragged"] = {"lens": lens, "identical": True}

    # int8 KV pool
    cfg, engines = _fp32_pair(block_size=8, kv_dtype="int8")
    toks = {}
    for kern, eng in engines.items():
        first = eng.put([0, 1], [prompts[2], prompts[4]])
        starts = [int(np.argmax(first[0])), int(np.argmax(first[1]))]
        out = eng.decode_batch([0, 1], starts, steps=6)
        toks[kern] = np.stack([out[0], out[1]])
    check(np.array_equal(toks["pallas"], toks["xla"]),
          "int8-KV greedy tokens diverged",
          {"pallas": toks["pallas"].tolist(), "xla": toks["xla"].tolist()})
    detail["int8kv"] = {"identical": True}

    # spec-verify (the wide-decode shape) on repetitive text so drafts fire
    cfg, engines = _fp32_pair(
        block_size=8, speculative={"enabled": True, "ngram": 2,
                                   "max_draft": 3, "fallback_steps": 2})
    rep = np.tile(rng.integers(1, cfg.vocab_size, 3), 7).astype(np.int32)
    toks = {}
    for kern, eng in engines.items():
        first = eng.put([0], [rep])
        out = eng.decode_batch([0], [int(np.argmax(first[0]))], steps=8,
                               speculative=True)
        toks[kern] = out[0]
        check(eng.spec_stats["fused"] == (1 if kern == "pallas" else 0),
              "spec_stats fused flag wrong",
              {"kernel": kern, "stats": dict(eng.spec_stats)})
    check(np.array_equal(toks["pallas"], toks["xla"]),
          "spec-verify greedy tokens diverged",
          {"pallas": toks["pallas"].tolist(), "xla": toks["xla"].tolist()})
    detail["spec_verify"] = {"identical": True}
    return detail


def scenario_fused_fence() -> dict:
    """Demote→promote through the FUSED prologue: same greedy tokens as the
    standalone-fence xla path, saved dispatches counted."""
    import numpy as np

    cfg, engines = _fp32_pair(
        block_size=8, max_sequences=4, max_seq_len=96,
        prefix_cache={"enabled": True,
                      "tiers": {"enabled": True, "host_mb": 8.0}})
    rng = np.random.default_rng(5)
    shared = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)  # 3 blocks
    sfx = rng.integers(1, cfg.vocab_size, 4).astype(np.int32)
    toks, reports = {}, {}
    for kern, eng in engines.items():
        # publish the shared prefix, flush, demote everything to host
        eng.put([0], [np.concatenate([shared, sfx])])
        eng.flush([0])
        pc = eng.prefix_cache
        pc.evict(pc.evictable_blocks())
        # a fresh request re-attaches the demoted prefix: the promotions
        # must fence through the (fused, for pallas) prologue of the next
        # dispatch before any attention read
        first = eng.put([1], [np.concatenate([shared, sfx])])
        out = eng.decode_batch([1], [int(np.argmax(first[1]))], steps=6)
        toks[kern] = out[1]
        reports[kern] = eng.tier_report()
        eng.close()
    check(np.array_equal(toks["pallas"], toks["xla"]),
          "fused-fence greedy tokens diverged",
          {"pallas": toks["pallas"].tolist(), "xla": toks["xla"].tolist()})
    check(reports["pallas"]["fused_prologue_dispatches_saved"] >= 1,
          "fused prologue saved no dispatches", reports["pallas"])
    check(reports["xla"]["fused_prologue_dispatches_saved"] == 0,
          "xla path claimed fused dispatches", reports["xla"])
    return {"identical": True,
            "saved_dispatches":
                reports["pallas"]["fused_prologue_dispatches_saved"]}


SCENARIOS = {
    "parity": scenario_parity,
    "fused-fence": scenario_fused_fence,
}


def run_scenario(name: str) -> dict:
    fn = SCENARIOS.get(name)
    if fn is None:
        raise SystemExit(f"unknown scenario {name!r} "
                         f"(have: {', '.join(SCENARIOS)})")
    t0 = time.perf_counter()
    try:
        detail = fn()
        ok, err = True, None
    except DrillFailure as e:
        detail, ok, err = None, False, str(e)
    return {"scenario": name, "ok": ok, "error": err, "detail": detail,
            "elapsed_s": round(time.perf_counter() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", help="which drill to run")
    ap.add_argument("--all", action="store_true", help="run every scenario")
    ap.add_argument("--list", action="store_true", help="list scenarios")
    args = ap.parse_args(argv)
    if args.list:
        for name, fn in SCENARIOS.items():
            print(f"{name}: {fn.__doc__.splitlines()[0]}")
        return 0
    names = list(SCENARIOS) if args.all else (
        [args.scenario] if args.scenario else None)
    if not names:
        ap.error("pass --scenario NAME, --all, or --list")
    rc = 0
    for name in names:
        verdict = run_scenario(name)
        print(json.dumps(verdict))
        if not verdict["ok"]:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
