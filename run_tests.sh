#!/bin/bash
# Test runner: forces a pure-CPU 8-device virtual topology (the analog of
# the reference's local[4] 4-node simulation, TEST/optim/DistriOptimizerSpec
# .scala:38-47), so every mesh/collective path runs without a chip.
#
# After the pytest tier, the graft-lint static gate runs: every zoo model
# and parallel plan traced to a jaxpr and audited offline
# (docs/graft_lint.md) — a lint finding fails the run like a test failure.
env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS}" \
  python -m pytest tests/ -q "$@"
pytest_rc=$?

python tools/graft_lint.py --all --json
lint_rc=$?

# fast deviceless autotune smoke (docs/autotune.md): one shape per
# kernel family, two candidates each, through the same Mosaic pipeline
# the full sweep uses — catches candidate-space / injection-seam
# regressions without hardware.  Writes to /tmp, never the repo table.
timeout -k 10 600 python tools/autotune.py --smoke
tune_rc=$?

# workload replay determinism smoke (docs/observability.md §Request
# X-ray): record a 64-request synthetic decode stream, replay it
# through a fresh engine, and assert bit-equal token streams, the
# recording run's recompile count, and zero steady-state recompiles.
env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS}" \
  timeout -k 10 600 python tools/replay.py --selftest 64
replay_rc=$?

[ $pytest_rc -ne 0 ] && exit $pytest_rc
[ $lint_rc -ne 0 ] && exit $lint_rc
[ $tune_rc -ne 0 ] && exit $tune_rc
exit $replay_rc
