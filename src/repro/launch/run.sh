#!/usr/bin/env bash
# Production launch wrapper (DESIGN.md §15): the hygiene that must be in
# place BEFORE the python interpreter execs — pair of launch/env.py,
# which handles the in-process half (XLA_FLAGS merge, dtype pins).
#
#   launch/run.sh serve --reduced --continuous --backend auto ...
#   launch/run.sh train --reduced --steps 20 ...
#   REPRO_ENTRY=module.path launch/run.sh -- <args>   # custom entrypoint
#
# Everything uses ":-" defaults: an operator's exported value wins.
set -euo pipefail

# -- tcmalloc: the linker reads LD_PRELOAD at exec time, so this is the
#    one knob launch/env.py cannot set for you.  glibc malloc fragments
#    badly under multi-GB arena churn; preload tcmalloc when present.
if [[ -z "${LD_PRELOAD:-}" ]]; then
  for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
            /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
            /usr/lib/libtcmalloc.so.4; do
    if [[ -e "$so" ]]; then
      export LD_PRELOAD="$so"
      break
    fi
  done
fi
# silence tcmalloc's >1GB allocation reports (params trip it constantly)
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"

# -- log + dtype hygiene (env.py setdefaults these too; exporting here
#    covers tooling that spawns before main(), e.g. pytest plugins)
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-2}"
export JAX_DEFAULT_DTYPE_BITS="${JAX_DEFAULT_DTYPE_BITS:-32}"
export JAX_ENABLE_X64="${JAX_ENABLE_X64:-0}"

entry="${REPRO_ENTRY:-}"
if [[ -z "$entry" ]]; then
  case "${1:-serve}" in
    serve|train) entry="repro.launch.$1"; shift ;;
    --) entry="repro.launch.serve"; shift ;;
    *)  entry="repro.launch.serve" ;;
  esac
else
  [[ "${1:-}" == "--" ]] && shift
fi

exec python -m "$entry" "$@"
