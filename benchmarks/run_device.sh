#!/usr/bin/env bash
# TPU launcher for the kernel tile-autotune sweep campaign.
#
# The CI sweep runs under the Pallas interpreter (correctness only); this
# wrapper pins the TPU platform and the allocator/XLA environment so the
# SAME sweep produces meaningful numbers on a TPU:
#
#   benchmarks/run_device.sh --sweep                       # full (M,d,K) grid
#   benchmarks/run_device.sh --sweep --kernel assign
#   benchmarks/run_device.sh --sweep --shapes '262144,64,256'
#
# Winners persist to $REPRO_TUNE_CACHE (default: benchmarks/tune_cache.json
# next to this script) — copy stable rows into
# src/repro/kernels/tune_table.py in a reviewed diff to refresh the
# committed per-device defaults.
set -euo pipefail

cd "$(dirname "$0")/.."

# tcmalloc: glibc malloc fragments badly under the host-side staging XLA
# does around big device transfers; preload when present, else proceed.
for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
          /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4; do
  if [[ -e "$so" ]]; then
    export LD_PRELOAD="$so"
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
    break
  fi
done

# quiet the TF/XLA log spew so sweep output stays readable
export TF_CPP_MIN_LOG_LEVEL=4

# the kernels, not the interpreter: pin the TPU platform, so a host with
# no TPU fails at start-up instead of quietly timing the CPU (kernels
# interpret exactly when the backend is not a TPU)
export JAX_PLATFORMS=tpu

# keep f32 f32 — an accidental x64 default doubles every byte count the
# roofline model predicts
export JAX_ENABLE_X64=0

# leave XLA_FLAGS caller-extensible but make sure we never inherit a
# host-device-count override from a CPU-CI shell
if [[ "${XLA_FLAGS:-}" == *force_host_platform_device_count* ]]; then
  echo "warning: dropping inherited XLA_FLAGS ($XLA_FLAGS)" >&2
  unset XLA_FLAGS
fi

export REPRO_TUNE_CACHE="${REPRO_TUNE_CACHE:-benchmarks/tune_cache.json}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "# device sweep: REPRO_TUNE_CACHE=$REPRO_TUNE_CACHE" >&2
exec python3 -m benchmarks.bench_kernels "$@"
