#!/bin/bash
# One-command serving demo (the asyncEASGD.sh pattern for inference):
# train a small LM for a few steps, serve it with continuous batching,
# fire CONCURRENCY parallel requests at it, then SIGTERM the server and
# let the drain finish the in-flight requests.
#   PORT=9123 CONCURRENCY=8 ./serve_lm.sh
# One process per chip: both roles run on the CPU here.  On a TPU host
# only lm.py may take --tpu (it then owns the chip); lm_client.py never
# computes and stays chip-free.
cd "$(dirname "$0")"
PORT=${PORT:-9123}
SLOTS=${SLOTS:-4}
CONCURRENCY=${CONCURRENCY:-4}
STEPS=${STEPS:-5}
MAXNEW=${MAXNEW:-16}

python lm.py --dp 1 --sp 1 --tp 1 --steps "$STEPS" \
  --serve "$SLOTS" --servePort "$PORT" &
SERVER=$!
trap 'kill $SERVER 2>/dev/null' EXIT

python lm_client.py --port "$PORT" --concurrency "$CONCURRENCY" \
  --maxNew "$MAXNEW"
RC=$?

kill -TERM $SERVER 2>/dev/null   # graceful drain (ha.install_signal_flush)
wait $SERVER
exit $RC
