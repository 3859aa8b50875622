"""Readings for a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 [--control]

For each seed, in this one process: the cell's set-up and window exactly
as ``run.py`` makes them, then the check's sample of finished requests
through the plain reference.  It prints, per seed, the program's
served-token gaps (the lower readings of the limits) and, with
``--control``, the control's: on the same prompts and served tokens the
reference computed in float8 (``f8``), reading at each position the gap
of the token float8 puts first (the upper readings), beside the same
readings of a float8 activation path (``f8act``) and of the reference
rounded to bfloat16 (``bf16``, how far the configuration's own
precision moves this model).  As a second witness it
also compares the program's own logits at each sampled prompt's last
position (``Engine.prefill_logits``, the chunk program) with the
reference's, and the float8 reference's with the reference's.  Each
reading goes through the check's own judgement (``check.judge``) against
the cell's limits in ``bench/limits/<cell>.json``, where there are any:
the program's has to come out correct, the control's not.  The
benchmark's own runs never run any of this.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def logit_error(got: np.ndarray, ref: np.ndarray) -> dict:
    d = got - ref
    return {"max_rel": float(np.abs(d).max() / np.abs(ref).max()),
            "rms_rel": float(np.sqrt(np.mean(d * d)) / ref.std())}


def main(argv) -> int:
    import argparse

    from harness import check, driver
    from harness.cells import Benchmark

    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    bench = Benchmark()
    wl = bench.workload(args.workload)
    limits_path = bench.dir / "limits" / f"{args.workload}.json"
    limits = (json.loads(limits_path.read_text())["limits"]
              if limits_path.is_file() else {})
    devices = driver.require_chip(int(wl["chips"]))
    driver.log(f"compile cache: {driver._enable_cache()}")
    for seed in (int(s) for s in args.seeds.split(",")):
        witness = {}

        def before_free(engine, client, pick):
            # free every slot, then the chunk program's own last-position
            # logits for each sampled prompt
            for rid in list(client.by_rid):
                engine.cancel(rid)
            for i in pick:
                lg = engine.prefill_logits(client.plan[i].prompt)
                witness[i] = np.asarray(lg[:engine.cfg.vocab], np.float32)

        out = driver.serve(bench, wl, seed, args.seconds, False, devices,
                           time.time(), before_free=before_free)
        ref = bench.reference(out.spec["reference"])
        row = {"seed": seed, "requests": len(out.requests),
               "metrics": {k: v["value"]
                           for k, v in out.result["metrics"].items()}}
        if out.requests:
            hi = ref.served_logits(out.spec, seed, out.requests)
            gaps = check.served_gaps(hi, [s for _, s in out.requests])
            row["program"] = check.stats(gaps)
            row["program_correct"] = check.judge(row["program"], limits)[0]
            row["tokens"] = int(gaps.size)
            row["program_prefill_logits"] = [
                logit_error(w, lg[0]) for w, lg in zip(witness.values(), hi)]
            if args.control:
                for mode in ("f8", "f8act", "bf16"):
                    lo = ref.served_logits(out.spec, seed, out.requests,
                                           lowp=mode)
                    row[mode] = check.stats(check.control_gaps(hi, lo))
                    row[mode + "_correct"] = check.judge(row[mode],
                                                         limits)[0]
                    row[mode + "_prefill_logits"] = [
                        logit_error(a[0], b[0]) for a, b in zip(lo, hi)]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
