#!/bin/bash
# Local/dev runner probing TPU hardware — counterpart of the
# reference's docker/run.sh hardware probe (GPU/NCS2/HDDL device
# cgroups, reference docker/run.sh:83-119) for TPU VMs.

set -euo pipefail

MODE="${RUN_MODE:-EVA}"

probe_tpu() {
    # TPU VM device nodes: /dev/accel* (v4+/v5) or vfio-bound PCI.
    if compgen -G "/dev/accel*" > /dev/null; then
        echo "found TPU device nodes: $(ls /dev/accel* | tr '\n' ' ')"
        return 0
    fi
    if [ -d /dev/vfio ] && compgen -G "/dev/vfio/*" > /dev/null; then
        echo "found vfio TPU devices"
        return 0
    fi
    return 1
}

# No silent CPU fallback: without TPU device nodes the run fails unless
# the caller asked for the CPU dry run with JAX_PLATFORMS=cpu (serve
# makes the same check on the backend JAX actually brings up).
if [ "${JAX_PLATFORMS:-}" = "cpu" ]; then
    export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"
elif ! probe_tpu; then
    echo "no TPU devices found; set JAX_PLATFORMS=cpu for the CPU dry run" >&2
    exit 1
fi

# Build native kernels if the toolchain is present.
if command -v g++ > /dev/null; then
    make -C "$(dirname "$0")/../native" >/dev/null 2>&1 || true
fi

echo "starting evam-tpu (mode=$MODE platform=${JAX_PLATFORMS:-tpu})"
exec python -m evam_tpu.cli.main serve --mode "$MODE" "$@"
