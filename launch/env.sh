#!/usr/bin/env bash
# Shared launch environment for host-platform (CPU) multi-device runs.
#
#   source launch/env.sh [NDEVICES]     # default 8
#
# Forces NDEVICES host CPU devices (XLA reads the flag once at backend init)
# and preloads tcmalloc when available — large-grid benchmarks allocate and
# free multi-GB halo-extended slabs per wave, where glibc malloc fragments.
# CPU-only: it pins JAX_PLATFORMS=cpu, so never source it for a chip run.
# Python-side equivalent: repro.launch.hostenv.
N="${1:-8}"
export XLA_FLAGS="--xla_force_host_platform_device_count=${N}"
export JAX_PLATFORMS=cpu
for lib in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
           /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
           /usr/lib/libtcmalloc.so.4; do
  if [ -e "${lib}" ]; then
    export LD_PRELOAD="${lib}"
    break
  fi
done
