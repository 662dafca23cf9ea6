#!/usr/bin/env bash
# Run the mesh/sharded-serving test family on N forced-host CPU devices
# with the XLA/JAX environment set up correctly — one command instead of
# remembering the flag soup:
#
#   scripts/run_mesh_tests.sh            # 8 virtual devices, mesh tests
#   MESH_DEVICES=4 scripts/run_mesh_tests.sh
#   scripts/run_mesh_tests.sh tests/test_serving_dist.py -k parity -x
#
# Notes:
#  * --xla_force_host_platform_device_count must be in XLA_FLAGS BEFORE
#    jax initializes (the multichip-dryrun trick; tests/conftest.py sets
#    8 itself, but bench workers / manual python runs do not).
#  * JAX_PLATFORMS=cpu: these are CPU-mesh tests; on a machine with a
#    chip they must not take it.
set -euo pipefail

N="${MESH_DEVICES:-8}"
cd "$(dirname "$0")/.."

ARGS=("$@")
if [ ${#ARGS[@]} -eq 0 ]; then
  ARGS=(tests/test_serving_dist.py tests/test_sp_prefill.py
        tests/test_quantized_collectives.py
        tests/test_distributed.py
        tests/test_pipeline.py tests/test_fleet_gpt2.py
        tests/test_gpt2_pipeline.py tests/test_moe.py
        tests/test_hybrid_gpt2_4d.py)
fi

exec env \
  XLA_FLAGS="--xla_force_host_platform_device_count=${N} ${XLA_FLAGS:-}" \
  JAX_PLATFORMS=cpu \
  python -m pytest -q -m 'not slow' -p no:cacheprovider "${ARGS[@]}"
