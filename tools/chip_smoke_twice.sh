#!/bin/bash
# Run chip_smoke.py twice in ONE chip-tool command, so both runs see the same
# compile-cache directory and the second shows the first run's executables
# hitting:   chiprun --timeout 2400 -- bash tools/chip_smoke_twice.sh
# Full logs land under chiprun_out/ (copied back by the tool).
mkdir -p chiprun_out
for i in 1 2; do
  python3 chip_smoke.py "$@" > chiprun_out/smoke_run$i.out 2> chiprun_out/smoke_run$i.err
  rc=$?
  echo "=== run $i rc=$rc"
  cat chiprun_out/smoke_run$i.out
  echo "--- stderr tail (run $i)"
  grep -v "^W0\|^I0" chiprun_out/smoke_run$i.err | tail -40 | cut -c1-400
  if [ $rc -ne 0 ]; then exit $rc; fi
done
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-<unset>}"
