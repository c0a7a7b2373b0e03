#!/bin/bash
# Counterpart of examples/AsyncEASGD.sh: parameter server + tester + 2
# worker clients on localhost.  The reference kills stale ports with fuser
# and derives the server IP from ifconfig; localhost + fresh port suffices
# here (multi-host: pass --host/--port to each role).
#
# One process per chip: every role here runs on the CPU (no --tpu, so
# setup_platform pins the CPU explicitly).  On a TPU host the server and
# the tester STAY on the CPU; launch by hand the client(s) that should
# compute on a chip, one --tpu client per chip — a second process that
# goes for a chip another one holds fails or hangs.
cd "$(dirname "$0")"
# --join-after S / --leave-after S: elastic membership drills
# (docs/ELASTIC.md).  Either flag switches the server to
# --concurrent --elastic; the joiner enters mid-run as client 3 through
# the Join? handshake, the leaver is client 2 departing gracefully via
# Leave? (pending delta flushed through the ledger, not dropped).
JOIN_AFTER=${JOIN_AFTER:-}
LEAVE_AFTER=${LEAVE_AFTER:-}
while [ $# -gt 0 ]; do
  case "$1" in
    --join-after)  JOIN_AFTER=$2; shift 2 ;;
    --leave-after) LEAVE_AFTER=$2; shift 2 ;;
    *) echo "usage: $0 [--join-after SECS] [--leave-after SECS]" >&2; exit 2 ;;
  esac
done
PORT=${PORT:-9500}
NODES=2
EPOCHS=${EPOCHS:-1}
BATCH=${BATCH:-16}
N=${N:-256}
MODEL=${MODEL:-mnist}
TAU=${TAU:-4}
# steps/epoch = (N/NODES)/BATCH; syncs = NODES*EPOCHS*(steps/tau)
STEPS_PER_EPOCH=$(( (N / NODES) / BATCH ))
# client sync counters run continuously across epochs
SYNCS=$(( NODES * ((EPOCHS * STEPS_PER_EPOCH) / TAU) ))
TESTTIME=${TESTTIME:-4}
NUMTESTS=$(( SYNCS / TESTTIME + 1 ))

common="--numNodes $NODES --port $PORT --numEpochs $EPOCHS --batchSize $BATCH \
  --numExamples $N --communicationTime $TAU --model $MODEL"
# CONCURRENT=1 serves clients on overlapped worker threads
# (AsyncEAServerConcurrent) instead of the reference's critical section
ELASTIC=
if [ -n "$JOIN_AFTER$LEAVE_AFTER" ]; then
  CONCURRENT=1   # elastic membership needs the concurrent server
  ELASTIC=1
fi
SERVER_FLAGS=${CONCURRENT:+--concurrent}
SERVER_FLAGS="$SERVER_FLAGS ${ELASTIC:+--elastic}"
# SHARDS=N stripes the center across N shard channels;
# clients negotiate the plan in the Enter? handshake automatically
SERVER_FLAGS="$SERVER_FLAGS ${SHARDS:+--shards $SHARDS}"
# CENTER_CKPT=dir turns on HA checkpointing of the center (+ one final
# flush on SIGTERM); CKPT_EVERY tunes the cadence.  STANDBY_PORT=p also
# launches a warm standby on that port tailing the same directory and
# points the clients' failover dial list at it (docs/HA.md).
SERVER_FLAGS="$SERVER_FLAGS ${CENTER_CKPT:+--centerCkpt $CENTER_CKPT}"
SERVER_FLAGS="$SERVER_FLAGS ${CKPT_EVERY:+--ckptEvery $CKPT_EVERY}"
CLIENT_FLAGS=${STANDBY_PORT:+--centers 127.0.0.1:$STANDBY_PORT}

# Membership drills make the served-sync count dynamic (a leaver serves
# fewer, a joiner more), so the tester's fixed push cadence cannot be
# precomputed: skip the eval channel, give the sync budget slack, and
# let the server stop when the fleet drains (or goes idle).
if [ -n "$ELASTIC" ]; then
  SYNCS=$(( SYNCS * 3 ))
  python easgd_server.py $common --numSyncs $SYNCS --syncTimeout 30 $SERVER_FLAGS &
else
  python easgd_server.py $common --tester --testTime $TESTTIME --numSyncs $SYNCS $SERVER_FLAGS &
fi
SERVER=$!
STANDBY=
if [ -n "$STANDBY_PORT" ] && [ -n "$CENTER_CKPT" ]; then
  # the standby binds its own port window now, promotes only when the
  # primary's checkpoints appear AND the fleet re-dials it
  python easgd_server.py $common --port $STANDBY_PORT --concurrent --standby \
    --watchPrimary 127.0.0.1:$PORT --syncTimeout 15 \
    --numSyncs $SYNCS $SERVER_FLAGS &
  STANDBY=$!
fi
# KILL_AFTER_CKPTS=n SIGTERMs the primary once n checkpoints are on disk
# (i.e. provably mid-serving with restorable state): the failover drill
# from docs/HA.md — final flush, standby promotes, clients re-dial it
if [ -n "$KILL_AFTER_CKPTS" ] && [ -n "$CENTER_CKPT" ]; then
  (
    while [ "$(ls "$CENTER_CKPT" 2>/dev/null | wc -l)" -lt "$KILL_AFTER_CKPTS" ]; do
      sleep 0.2
    done
    echo "[chaos] $KILL_AFTER_CKPTS checkpoints on disk; SIGTERM primary $SERVER"
    kill -TERM $SERVER
  ) &
fi
TESTER=
if [ -z "$ELASTIC" ]; then
  python easgd_tester.py $common --numTests $NUMTESTS &
  TESTER=$!
fi
python easgd_client.py $common --nodeIndex 1 --verbose $CLIENT_FLAGS &
C1=$!
# the leave drill rides client 2: it trains, announces Leave? after the
# deadline (flushing its in-flight delta), and exits cleanly
python easgd_client.py $common --nodeIndex 2 --verbose $CLIENT_FLAGS \
  ${LEAVE_AFTER:+--leaveAfter $LEAVE_AFTER} &
C2=$!
C3=
if [ -n "$JOIN_AFTER" ]; then
  # the join drill: a third client enters the running fleet via Join? —
  # the server assigns its cid and streams the live center before it
  # counts as a member (the join fence)
  ( sleep "$JOIN_AFTER"
    echo "[drill] client 3 joining the fleet after ${JOIN_AFTER}s"
    exec python easgd_client.py $common --nodeIndex 3 --joinFleet \
      --verbose $CLIENT_FLAGS ) &
  C3=$!
fi
wait $SERVER $TESTER $C1 $C2 $C3 $STANDBY
