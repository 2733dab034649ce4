#!/usr/bin/env bash
# Provision a Cloud TPU pod slice and bring up a SUPERVISED kubeml-tpu fleet
# on it — the counterpart of the reference's cluster bootstrap
# (ml/hack/cluster_config.sh installs Fission + prometheus + the Helm chart;
# here the fleet is one supervised process per TPU-VM host).
#
# Usage:
#   deploy/provision-tpu-pod.sh NAME ZONE ACCEL_TYPE [VERSION]
#   e.g. deploy/provision-tpu-pod.sh kubeml-pod us-east5-b v5litepod-16
#
# What it does:
#   1. creates the TPU VM (queued resource) if it does not exist;
#   2. rsyncs this repo to every host;
#   3. installs the supervised systemd unit on every host with the
#      coordinator env derived from worker 0 (restart-and-resume: the PS job
#      journal makes any crash/restart resume from the newest checkpoint);
#   4. prints the controller URL.
#
# Requirements: gcloud authenticated, a shared KUBEML_DATA_ROOT (GCS fuse or
# NFS) mounted at the same path on every host for datasets/functions/
# checkpoints — the same reachable-from-every-pod assumption the reference
# makes of Mongo/Redis.
set -euo pipefail

NAME=${1:?usage: provision-tpu-pod.sh NAME ZONE ACCEL_TYPE [VERSION]}
ZONE=${2:?zone}
ACCEL=${3:?accelerator type, e.g. v5litepod-16}
VERSION=${4:-tpu-ubuntu2204-base}
REPO=${KUBEML_REPO:-$(cd "$(dirname "$0")/.." && pwd)}
DATA_ROOT=${KUBEML_DATA_ROOT:-/var/lib/kubeml}
COORD_PORT=${KUBEML_COORD_PORT:-8476}

if ! gcloud compute tpus tpu-vm describe "$NAME" --zone "$ZONE" >/dev/null 2>&1; then
  echo "creating TPU VM $NAME ($ACCEL) in $ZONE..."
  gcloud compute tpus tpu-vm create "$NAME" --zone "$ZONE" \
    --accelerator-type "$ACCEL" --version "$VERSION"
fi

echo "discovering workers..."
N=$(gcloud compute tpus tpu-vm describe "$NAME" --zone "$ZONE" \
      --format="value(networkEndpoints.len())")
HOST0=$(gcloud compute tpus tpu-vm describe "$NAME" --zone "$ZONE" \
      --format="value(networkEndpoints[0].ipAddress)")
echo "  $N workers; leader $HOST0"

echo "syncing repo to all workers..."
# /opt is root-owned on stock images: create the destination writable for
# the SSH login user BEFORE the unprivileged scp
gcloud compute tpus tpu-vm ssh "$NAME" --zone "$ZONE" --worker=all \
  --command 'sudo mkdir -p /opt/kubeml-tpu && sudo chown "$USER" /opt/kubeml-tpu'
# ship SOURCE, not history/artifacts (.git dominates repo bytes)
STAGE=$(mktemp -d)
trap 'rm -rf "$STAGE"' EXIT
tar -C "$REPO" --exclude=.git --exclude='__pycache__' \
    --exclude='*.pyc' -cf - . | tar -C "$STAGE" -xf -
gcloud compute tpus tpu-vm scp --recurse "$STAGE"/. "$NAME":/opt/kubeml-tpu \
  --zone "$ZONE" --worker=all

echo "installing the supervised unit on every worker..."
pids=()
for i in $(seq 0 $((N - 1))); do
  gcloud compute tpus tpu-vm ssh "$NAME" --zone "$ZONE" --worker="$i" --command "
    sudo mkdir -p $DATA_ROOT &&
    sudo cp /opt/kubeml-tpu/deploy/systemd/kubeml-supervised.service /etc/systemd/system/ &&
    sudo mkdir -p /etc/systemd/system/kubeml-supervised.service.d &&
    printf '[Service]\nEnvironment=KUBEML_DATA_ROOT=$DATA_ROOT\nEnvironment=KUBEML_COORDINATOR=$HOST0:$COORD_PORT\nEnvironment=KUBEML_NUM_PROCESSES=$N\nEnvironment=KUBEML_PROCESS_ID=$i\n' \
      | sudo tee /etc/systemd/system/kubeml-supervised.service.d/override.conf >/dev/null &&
    sudo systemctl daemon-reload &&
    sudo systemctl enable --now kubeml-supervised
  " &
  pids+=($!)
done
# fail LOUDLY if any worker's install failed — a silently missing rank means
# a jax.distributed group that never forms
failed=0
for idx in "${!pids[@]}"; do
  wait "${pids[$idx]}" || { echo "ERROR: worker $idx install failed" >&2; failed=1; }
done
[ "$failed" -eq 0 ] || exit 1

echo "fleet up: controller at http://$HOST0:${KUBEML_CONTROLLER_PORT:-9090}"
echo "  submit:   kubeml --url http://$HOST0:9090 train ..."
echo "  logs:     gcloud compute tpus tpu-vm ssh $NAME --zone $ZONE --worker=0 \\"
echo "              --command 'journalctl -u kubeml-supervised -f'"
