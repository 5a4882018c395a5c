#!/bin/bash
# spark-submit wrapper that plumbs the framework's TPU environment into
# every executor — the incantation the reference documented per-example
# (its README spark-submit blocks), packaged once.
#
# Usage: ./submit_train.sh <app.py> [app args...]
# Env:   MASTER (default spark://$(hostname):7077),
#        SPARK_WORKER_INSTANCES (default 2), CHIPS_PER_NODE (default 1),
#        TOS_TPU_SERVER_HOST/PORT (optional control-plane pinning),
#        EXTRA_SPARK_CONF (optional, e.g. "--conf spark.speculation=true")
set -euo pipefail

[ $# -ge 1 ] || { echo "usage: $0 <app.py> [args...]" >&2; exit 2; }
APP="$1"; shift

MASTER="${MASTER:-spark://$(hostname):7077}"
WORKERS="${SPARK_WORKER_INSTANCES:-2}"
CHIPS_PER_NODE="${CHIPS_PER_NODE:-1}"

# executor env: TPU placement + optional control-plane pinning. The
# framework's pipeline/transform tasks claim disjoint chip groups
# themselves (pipeline._allocate_transform_chips); cluster.run carves
# chips via chips_per_node at reservation time.
ENV_CONF=()
[ -n "${TOS_TPU_SERVER_HOST:-}" ] && ENV_CONF+=(
  --conf "spark.executorEnv.TOS_TPU_SERVER_HOST=${TOS_TPU_SERVER_HOST}")
[ -n "${TOS_TPU_SERVER_PORT:-}" ] && ENV_CONF+=(
  --conf "spark.executorEnv.TOS_TPU_SERVER_PORT=${TOS_TPU_SERVER_PORT}")

exec "${SPARK_HOME}/bin/spark-submit" \
  --master "${MASTER}" \
  --deploy-mode client \
  --num-executors "${WORKERS}" \
  --executor-cores 1 \
  --conf spark.task.maxFailures=4 \
  --conf spark.dynamicAllocation.enabled=false \
  ${ENV_CONF[@]+"${ENV_CONF[@]}"} \
  ${EXTRA_SPARK_CONF:-} \
  "${APP}" \
  --cluster_size "${WORKERS}" \
  --chips_per_node "${CHIPS_PER_NODE}" \
  "$@"
