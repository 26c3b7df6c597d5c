#!/usr/bin/env bash
# Launcher for the TPU-native rebuild's layers — the role of the
# reference's deploy/bin/oryx-run.sh:194-286 (spark-submit / YARN
# distributed shell), re-targeted at TPU-VM / container hosts: layers are
# plain processes (python -m oryx_tpu <layer>) and cluster placement is
# handled by the GKE manifests in deploy/gke/ or by running this script
# on each host.
#
#   oryx-run.sh command [--option value] ...
#     command: batch | speed | serving | bus-serve | bus-setup |
#              bus-tail | bus-input | all
#     --conf        Oryx config file (default: ./oryx.conf)
#     --app-dir     extra dir on sys.path for config-named app classes
#                   (the --app-jar analogue)
#     --set         KEY=VALUE config override; repeatable
#     --input-file  for bus-input
#     --bind        for bus-serve (default 0.0.0.0:6378)
#     --data-dir    for bus-serve (topic log directory on this host)
#     --foreground  run in the foreground (default: nohup to logs/)
#     --chip        for all: the layer that owns the accelerator
#                   (serving | batch | speed; default serving)
#
# One accelerator, one owner. A TPU chip belongs to one process at a
# time: a second process that asks for it fails or hangs, and one that
# lets JAX choose ends up on the CPU without saying so. So every layer is
# told its platform through the environment, as in
# `JAX_PLATFORMS=tpu oryx-run.sh batch`: tpu makes JAX fail at start-up if
# the chip cannot be had, cpu makes a host-only process, and a layer
# started with neither refuses to carry on if JAX fell back to the CPU
# (oryx_tpu/parallel/distributed.py claim_devices). Each layer logs its
# platform at start and reports it at /healthz.
#
# `all` stands up a single-host pipeline: bus topics + serving + speed +
# batch, each as its own process with logs under ./logs/ — the quick-start
# topology for one TPU VM (docs/admin.md). On such a host the chip goes to
# ONE layer, by default serving (it answers from device memory all day);
# the other two start host-only. `--chip batch` trains on the chip and
# serves from the host instead. To do both on accelerators, give each its
# own host (or chip) and start it there with JAX_PLATFORMS=tpu.

set -euo pipefail

COMMAND="${1:-}"
[ -n "${COMMAND}" ] || { grep '^#   ' "$0" | sed 's/^#   //'; exit 1; }
shift

CONF="oryx.conf"
FOREGROUND=0
CHIP="serving"
PASS_ARGS=()
while (($#)); do
  case "$1" in
    --conf)       CONF="$2"; PASS_ARGS+=(--conf "$2"); shift 2 ;;
    --foreground) FOREGROUND=1; shift ;;
    --chip)       CHIP="$2"; shift 2 ;;
    --app-dir|--set|--input-file|--bind|--data-dir)
                  PASS_ARGS+=("$1" "$2"); shift 2 ;;
    *) echo "unknown option $1"; exit 1 ;;
  esac
done

PY="${ORYX_PYTHON:-python3}"
LOG_DIR="${ORYX_LOG_DIR:-logs}"
mkdir -p "${LOG_DIR}"

launch() {  # launch <name> <subcommand...>
  local name="$1"; shift
  if [ "${FOREGROUND}" = "1" ]; then
    exec "${PY}" -m oryx_tpu "$@"
  fi
  nohup "${PY}" -m oryx_tpu "$@" >"${LOG_DIR}/${name}.log" 2>&1 &
  echo $! > "${LOG_DIR}/${name}.pid"
  echo "${name}: pid $(cat "${LOG_DIR}/${name}.pid") log ${LOG_DIR}/${name}.log"
}

case "${COMMAND}" in
  batch|speed|serving|bus-serve)
    launch "${COMMAND}" "${COMMAND}" "${PASS_ARGS[@]}"
    ;;
  bus-setup|bus-tail|bus-input)
    exec "${PY}" -m oryx_tpu "${COMMAND}" "${PASS_ARGS[@]}"
    ;;
  all)
    # single-host pipeline; bus topics must exist before layers attach
    case "${CHIP}" in
      serving|batch|speed) ;;
      *) echo "--chip must be serving, batch or speed, got ${CHIP}"; exit 1 ;;
    esac
    "${PY}" -m oryx_tpu bus-setup "${PASS_ARGS[@]}"
    OWNER_PLATFORM="${JAX_PLATFORMS:-tpu}"
    for layer in serving speed batch; do
      # the owner asks for the accelerator (the caller's JAX_PLATFORMS,
      # else tpu) and fails if it cannot have it; the others never touch it
      if [ "${layer}" = "${CHIP}" ]; then
        export JAX_PLATFORMS="${OWNER_PLATFORM}"
      else
        export JAX_PLATFORMS=cpu
      fi
      launch "${layer}" "${layer}" "${PASS_ARGS[@]}"
      echo "${layer}: JAX_PLATFORMS=${JAX_PLATFORMS}"
    done
    echo "pipeline up; stop with: kill \$(cat ${LOG_DIR}/*.pid)"
    ;;
  *)
    echo "unknown command ${COMMAND}"; exit 1 ;;
esac
