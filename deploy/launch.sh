#!/bin/sh
# Serving entrypoint: host-level tuning, then exec the launcher.
#
#   launch.sh [--entry MODULE] [--dry-run] <launcher args...>
#
# Defaults to `python -m repro.launch.serve`; pass
# `--entry repro.launch.distributed` for the multi-process harness.
# `--dry-run` prints the environment and command instead of running
# (used by CI on runners without docker).
#
# Tuning (see docs/deployment.md):
#   * tcmalloc via LD_PRELOAD when present — glibc malloc arena churn
#     slows XLA's large transient host allocations;
#   * TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD raised so numpy buffers
#     don't spam allocation warnings;
#   * TF_CPP_MIN_LOG_LEVEL=4 silences XLA's C++ chatter;
#   * JAX_PLATFORMS is left as given: JAX uses the accelerator the
#     installation finds, and JAX_PLATFORMS=cpu asks for the CPU;
#   * REPRO_HOST_DEVICES=N emulates N devices on CPU
#     (--xla_force_host_platform_device_count) for mesh serving.
set -eu

ENTRY="repro.launch.serve"
DRY_RUN=0
while [ $# -gt 0 ]; do
    case "$1" in
        --entry) ENTRY="$2"; shift 2 ;;
        --dry-run) DRY_RUN=1; shift ;;
        *) break ;;
    esac
done

for lib in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
           /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
           /usr/lib/libtcmalloc.so.4; do
    if [ -r "$lib" ]; then
        LD_PRELOAD="$lib${LD_PRELOAD:+:$LD_PRELOAD}"
        export LD_PRELOAD
        break
    fi
done
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

HOST_DEVICES="${REPRO_HOST_DEVICES:-1}"
case "${XLA_FLAGS:-}" in
    *xla_force_host_platform_device_count*) ;;
    *) export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=$HOST_DEVICES" ;;
esac

if [ "$DRY_RUN" = 1 ]; then
    echo "launch.sh dry run:"
    echo "  LD_PRELOAD=${LD_PRELOAD:-<none>}"
    echo "  TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=$TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"
    echo "  TF_CPP_MIN_LOG_LEVEL=$TF_CPP_MIN_LOG_LEVEL"
    echo "  JAX_PLATFORMS=${JAX_PLATFORMS:-<unset>}"
    echo "  XLA_FLAGS=$XLA_FLAGS"
    echo "  exec: python -m $ENTRY $*"
    exit 0
fi

exec python -m "$ENTRY" "$@"
