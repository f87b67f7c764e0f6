"""Record ``zero3_4chip_planes.json.gz`` on a host with four chips: a traced
run of ``mistral7b_train_zero3_4chip`` through ``benchmarks/run.py``'s own
``main`` (its lines are printed as ever, the result line last but one), then
from that run's trace the second and third run of the step program on every
device plane, every ``XLA Ops`` event inside them by instruction name (times
in ns from the first kept event), and the step-program row's record.

    python3 benchmarks/testdata/record_zero3_planes.py <out.json.gz> \
        --workload mistral7b_train_zero3_4chip --seed <n> --seconds 20 --trace 1

The last line printed says what was kept.
"""

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out: str, argv) -> int:
    from benchmarks import harness  # noqa: F401  (starts the set-up clock)
    from benchmarks import run

    rc = run.main(argv)
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce
    from benchmarks.readers import program
    from deepspeed_tpu.observability import steplog

    cell = argv[argv.index("--workload") + 1]
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")][-1].collectives()
    sums = steplog.collective_sums(rows)
    keep = {"record": rows,
            "calls_per_step": sums["collective_calls_per_step"],
            "bytes_per_step": sums["collective_bytes_per_step"],
            "planes": {}}
    picked = {}
    for plane in ProfileData.from_file(program.xplane_path(cell)).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted((int(e.start_ns), int(e.duration_ns), e.name)
                      for e in lines[program.MODULES_LINE].events
                      if program.STEP_MODULE.match(e.name))[1:3]
        lo, hi = mods[0][0], mods[-1][0] + mods[-1][1]
        ops = [(trace_reduce.split_hlo_name(e.name)[0], int(e.start_ns),
                int(e.duration_ns))
               for e in lines[trace_reduce.OPS_LINE].events
               if lo <= int(e.start_ns)
               and int(e.start_ns) + int(e.duration_ns) <= hi]
        picked[plane.name] = (mods, ops)
    if not picked:      # a rehearsal on the CPU has no device plane
        print(json.dumps({"recorded": None, "planes": 0}), flush=True)
        return rc
    t0 = min(mods[0][0] for mods, _ in picked.values())
    for name, (mods, ops) in picked.items():
        keep["planes"][name] = {
            "modules": [(n, s - t0, d) for s, d, n in mods],
            "ops": [(n, s - t0, d) for n, s, d in ops]}
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with gzip.open(out, "wt") as f:
        json.dump(keep, f, separators=(",", ":"))
    print(json.dumps({"recorded": out, "planes": len(picked),
                      "events": sum(len(o) for _, o in picked.values()),
                      "bytes": os.path.getsize(out)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
