"""What a tier-1 run cost, from the junit XML it writes (``tools/run_tier1.sh``
leaves it at ``/tmp/_t1.xml``), in ROADMAP.md D0's units: worker-seconds in
all, the whole-cell compiles for a described v5e (budgeted as measured, and
counted apart from their file), the dearest ten files, and every model's file
over its 150.

    python tools/tier1_cost.py /tmp/_t1.xml
"""

import collections
import sys
import xml.etree.ElementTree as ET

MODEL_FILES = ("test_kanana", "test_granite", "test_nemotron_h", "test_lfm2", "test_olmo_hybrid",
               "test_keye_vl2", "test_ling3", "test_mellum", "test_looped")
MODEL_BUDGET = 150.0

files, compiles = collections.Counter(), []
for case in ET.parse(sys.argv[1]).iter("testcase"):
    if case.get("name").endswith("_cells_step_program_compiles_for_v5e"):
        compiles.append(float(case.get("time")))
    else:
        files[case.get("classname").split(".")[2]] += float(case.get("time"))
print(f"total {sum(files.values()) + sum(compiles):.0f} worker-s; "
      f"model files {sum(files[m] for m in MODEL_FILES):.0f}; whole-cell compiles "
      f"{sum(compiles):.0f} ({', '.join(f'{s:.0f}' for s in sorted(compiles, reverse=True))})")
for name, secs in files.most_common(10):
    print(f"{secs:8.1f}  {name}")
for name in MODEL_FILES:
    if files[name] > MODEL_BUDGET:
        print(f"OVER  {name} {files[name]:.0f} > {MODEL_BUDGET:.0f}")
