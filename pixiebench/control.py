"""Readings that set the check's limits: the program and the control.

    python3 -m pixiebench.control --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed, in one process: the cell is set up as a run sets it up,
driven for a short window at its own load, and the run's sample of
answers is compared with the reference, as ``run`` does.  Beside it the
control takes the program's place, compared in the same way on the same
requests: on a walk, the reference itself with one stated guarantee broken
(``bias_beta`` 0: the walk ignores the user's language); on a ranked
configuration, the reference's stage 2 one precision down, its heads'
products taken from bfloat16 inputs (the step a float32 product takes at
the chip's default precision).  Prints per seed the mismatch share and the
widest gap of each.  The program's readings give the lower end of the
``mismatch_share`` limit, the control's its upper end.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from pixiebench import registry, run


def control_gaps(hg, served, server_seed, walk, chunk_steps, top_k,
                 stage2=None):
    """Gaps of the control's answers (bias off; with ``stage2``, the heads
    in bfloat16) to the reference's."""
    import ml_dtypes

    from pixiebench import reference

    broken = dict(walk, bias_beta=0.0)
    out = []
    for s in served:
        key = reference.request_key(server_seed, s.req_id)
        want = reference.recommend(hg, s.pins, s.weights, s.feat, key, walk,
                                   chunk_steps)
        if stage2 is not None:
            got = reference.rank(hg, want, stage2,
                                 head_dtype=ml_dtypes.bfloat16)
            out.append(reference.rank_gap(got.scores, got.ids,
                                          reference.rank(hg, want, stage2)))
            continue
        got = reference.recommend(hg, s.pins, s.weights, s.feat, key, broken,
                                  chunk_steps)
        order = np.lexsort((got.ids, -got.scores))[:top_k]
        out.append(reference.answer_gap(got.scores[order], got.ids[order],
                                        want))
    return np.asarray(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(registry.ROOT / "src"))
    from pixiebench import check, loadgen

    bench = registry.load_benchmark()
    cell = registry.cell(bench, args.workload)
    config = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    tol = config["check"]["rank_gap_tol" if "ranker" in config
                          else "gap_tol"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        c = run.set_up(cell, config, traffic, seed)
        reqs = loadgen.schedule(traffic, seed, args.seconds, c.offsets)
        rec = run.measure(c, reqs, trace=False).rec
        chunk_steps = c.server.cfg.chunk_steps
        hg, served, st2 = run.release_and_sample(c, rec, reqs)
        gaps = check.gaps(hg, served, c.server_seed, config["walk"],
                          chunk_steps, st2)
        row = {"seed": seed, "compared": len(served),
               "failed": int(rec["failed"].sum()),
               "program_mismatch_share": float(np.mean(gaps > tol)),
               "program_widest_gap": float(gaps.max())}
        cg = control_gaps(hg, served, c.server_seed, config["walk"],
                          chunk_steps, config["walk"]["top_k"], st2)
        row.update(control_mismatch_share=float(np.mean(cg > tol)),
                   control_narrowest_gap=float(cg.min()))
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        del c, hg, served
    return 0


if __name__ == "__main__":
    sys.exit(main())
