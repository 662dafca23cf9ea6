"""The lower-precision control of kimi_linear_48b_ep2.serve_decode128 (PR 26):
the whole cell through benchmark/run.py, with the engine's recurrent-state
store rounded to bf16 after every write
(tests/test_kimi_linear_check.bf16_state_store).  It must print
`"correct": false` with the state limit, and nothing else, among its
`[wrong]` lines.  From the root of a checkout, on the chip:

    chiprun -- python3 scripts/kimi_control_bf16_state.py \
        --workload kimi_linear_48b_ep2.serve_decode128 --seed <n> \
        --seconds 10 --trace 0
"""
import runpy
import sys

for p in ("benchmark", ".", "tests"):
    sys.path.insert(0, p)
from test_kimi_linear_check import bf16_state_store  # noqa: E402

sys.argv = ["benchmark/run.py"] + sys.argv[1:]
with bf16_state_store():
    runpy.run_path("benchmark/run.py", run_name="__main__")
