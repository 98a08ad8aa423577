#!/usr/bin/env bash
# Round-5 second TPU work queue: local_topk operating-regime arms, the
# hard-v2 accuracy-vs-compression curve, and the CIFAR round-shape grid
# — chained so the chip never idles between studies. (The ImageNet round
# profile it once ran is now a traced cell of the benchmark:
# perfbench/run.py --trace 1, read per phase by harness/phase_reader.py.)
set -uo pipefail
cd "$(dirname "$0")/.."

bash scripts/local_topk_arms.sh lr01 efnone lr003 \
    2>&1 | tee runs/local_topk_arms.out
bash scripts/hardv2_curve.sh c1m c2m c4m c8m c2m_sub \
    2>&1 | tee runs/hardv2_curve.out
python scripts/round_shape_grid.py 2>&1 | tee runs/round_shape_grid.out
echo QUEUE2_DONE
