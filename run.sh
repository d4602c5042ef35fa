#!/bin/bash
# Smoke/manual training launcher — entrypoint preserved from the
# reference's run.sh (reference run.sh:1-47), re-expressed TPU-native:
# the mpirun/Horovod/NCCL process-launch block (reference run.sh:20-32)
# collapses into ONE SPMD process per host; parallelism comes from the
# jax.sharding mesh, rank/world-size from JobSet env (COORDINATOR_ADDRESS,
# NUM_PROCESSES, PROCESS_ID) instead of an mpirun hostfile.
#
# Defaults run the single-process smoke (BASELINE.json config 1).
# Env overrides:
#   DATA_DIR       dataset root (default /efs/data; reference run.sh:7)
#   LOG_DIR        run-dir root (default /efs;     reference run.sh:9)
#   FILE_SYS       label in the run id (default efs)
#   NUM_HOSTS      host count (JobSet replicas; reference workers :3)
#   CHIPS_PER_HOST chips per host (≙ WORKER_GPU_COUNT=8, run.sh:4; v5e=4)
#   MODE_MASK      True|False — False = Faster-RCNN smoke
#   SYNTHETIC      1 → generated data, no dataset on disk
#   EXTRA_CONFIG   extra KEY=VALUE overrides appended verbatim

set -e

NUM_HOSTS=${NUM_HOSTS:-1}
CHIPS_PER_HOST=${CHIPS_PER_HOST:-1}
NUM_PARALLEL=$(( NUM_HOSTS * CHIPS_PER_HOST ))

DATA_DIR=${DATA_DIR:-/efs/data}
FILE_SYS=${FILE_SYS:-efs}
LOG_DIR=${LOG_DIR:-/efs}
MODE_MASK=${MODE_MASK:-True}
BATCH_NORM=${BATCH_NORM:-FreezeBN}

DATE=`date '+%Y-%m-%d-%H-%M-%S'`
RUN_ID=${RUN_ID:-mask-rcnn-coco-$NUM_PARALLEL-$FILE_SYS-$DATE}

# epoch coupling preserved: 120000 images / world size (run.sh:15)
STEPS_PER_EPOCH=$(( 120000 / NUM_PARALLEL ))

SYNTH_FLAG=""
if [ "${SYNTHETIC:-0}" = "1" ]; then
  SYNTH_FLAG="--synthetic"
fi

# pretrained init only when the npz is staged (synthetic/smoke runs
# train from scratch; real runs fail loudly in the loader if missing)
BACKBONE_NPZ=$DATA_DIR/pretrained-models/ImageNet-R50-AlignPadding.npz
BACKBONE_ARG="BACKBONE.WEIGHTS=$BACKBONE_NPZ"
if [ "${SYNTHETIC:-0}" = "1" ] && [ ! -f "$BACKBONE_NPZ" ]; then
  BACKBONE_ARG="BACKBONE.WEIGHTS="
fi

echo "Training started:" `date '+%Y-%m-%d-%H-%M-%S'`

# the argv shape below mirrors reference run.sh:33-45; TRAINER=horovod
# becomes TRAINER=spmd; the NCCL/Horovod env tuning has no analogue
# (XLA combines the gradient all-reduces itself)
python3 -m eksml_tpu.train \
  --logdir $LOG_DIR/$RUN_ID/train_log/maskrcnn \
  $SYNTH_FLAG \
  --config MODE_MASK=$MODE_MASK \
  MODE_FPN=True \
  DATA.BASEDIR=$DATA_DIR \
  "DATA.TRAIN=[\"train2017\"]" \
  DATA.VAL=val2017 \
  TRAIN.EVAL_PERIOD=1 \
  TRAIN.STEPS_PER_EPOCH=$STEPS_PER_EPOCH \
  "TRAIN.LR_SCHEDULE=[120000,160000,180000]" \
  TRAIN.NUM_CHIPS=$NUM_PARALLEL \
  TRAIN.CHIPS_PER_HOST=$CHIPS_PER_HOST \
  "$BACKBONE_ARG" \
  BACKBONE.NORM=$BATCH_NORM \
  TRAINER=spmd \
  ${EXTRA_CONFIG}

echo "Training finished:" `date '+%Y-%m-%d-%H-%M-%S'`
