#!/usr/bin/env bash
# Per-host launcher for multi-host TPU jobs — the TPU analog of the
# reference's scripts/launch.sh:137-171 (torchrun wrapper + NVSHMEM env).
#
# On TPU there is no torchrun: every host of a pod slice runs the SAME
# program and jax.distributed.initialize() rendezvouses them. This script
# normalizes the environment, then execs the given python program on THIS
# host. Fan it out to all hosts with your scheduler (GKE JobSet indexed
# pods, or gcloud's --worker=all, below).
#
# Single v5e-8 host (8 chips, 1 process):
#   bash scripts/launch.sh your_script.py [args...]
#
# One pod slice, N hosts (e.g. v5e-16 = 4 hosts x 4 chips) via gcloud:
#   gcloud compute tpus tpu-vm ssh $TPU_NAME --worker=all --command \
#     "cd /path/to/repo && bash scripts/launch.sh your_script.py"
#   (Cloud TPU metadata provides the rendezvous; initialize_distributed()
#    with no args lets jax auto-detect coordinator/process_id/count.)
#
# Manual rendezvous (bare-metal / GKE without TPU metadata): export
#   JAX_COORDINATOR_ADDRESS=<host0-ip>:8476
#   JAX_NUM_PROCESSES=<total hosts>    JAX_PROCESS_ID=<this host's index>
# before invoking; runtime/mesh.py:initialize_distributed() reads these
# (the MASTER_ADDR/WORLD_SIZE/RANK analog).
#
# Two slices (DCN, "inter_node" scope): launch the same way on each slice
# with MEGASCALE coordination (multislice deployments set these for you;
# manual runs set MEGASCALE_COORDINATOR_ADDRESS + MEGASCALE_NUM_SLICES +
# MEGASCALE_SLICE_ID). Topology.detect() then reports num_slices > 1 and
# make_2d_mesh() lays out the ("dcn", "ici") axes so collectives ride ICI
# inside a slice and DCN across (runtime/mesh.py:110-161).
set -euo pipefail

if [[ $# -lt 1 ]]; then
  echo "usage: $0 <script.py> [args...]" >&2
  exit 1
fi

REPO_DIR="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="${REPO_DIR}${PYTHONPATH:+:$PYTHONPATH}"

# Persistent XLA compile cache — the same rule as
# tools/aot.enable_xla_compilation_cache: a directory the environment
# names wins; otherwise the fixed in-checkout .cache/jax (never $HOME, a
# temp name, a pid or a time: the path is part of the cache key). With N
# hosts compiling the same SPMD program, pointing it at a shared dir
# (NFS/GCS-fuse) makes host 1..N-1 deserialize what host 0 compiled.
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$REPO_DIR/.cache/jax}"

# Contextual-autotuner winners live beside it (they change what gets
# compiled); share across the job's hosts if possible (the vote is
# collective either way; a shared cache just skips re-tunes).
# TDT_AUTOTUNE=0 disables tuning entirely.
export TDT_AUTOTUNE_CACHE="${TDT_AUTOTUNE_CACHE:-$REPO_DIR/.cache/autotune.json}"

# Surface hangs rather than waiting forever on a lost host: a collective
# stuck longer than this dumps per-host stacks and aborts the job.
export JAX_DISTRIBUTED_INITIALIZATION_TIMEOUT="${JAX_DISTRIBUTED_INITIALIZATION_TIMEOUT:-300}"

echo "[launch.sh] host=$(hostname) repo=${REPO_DIR}" >&2
echo "[launch.sh] JAX_COORDINATOR_ADDRESS=${JAX_COORDINATOR_ADDRESS:-<auto>}" \
     "JAX_PROCESS_ID=${JAX_PROCESS_ID:-<auto>}/${JAX_NUM_PROCESSES:-<auto>}" >&2

exec python "$@"
