#!/bin/bash
# Multi-host topology on one machine: two PROCESSES (the reference's
# process-per-host shape, client_remote.sh) training over the TCP tree and
# ending with bitwise-identical params (compare the printed digests).
# For real multi-host runs see the flags in client_remote.py's docstring.
#
# One process per chip: "$@" goes to EVERY rank, so --tpu here would send
# all of them for the same chip(s) — the second one fails or hangs.  Run
# a rank on a chip by launching client_remote.py yourself, one --tpu
# process per chip.
case " $* " in
  *" --tpu "*)
    echo "client_remote.sh: --tpu would hand every rank the same chip;" \
         "launch one --tpu process per chip by hand" >&2
    exit 2 ;;
esac
cd "$(dirname "$0")"
PORT=${PORT:-9090}
N=${N:-2}
for i in $(seq 2 $N); do
  python client_remote.py --nodeIndex "$i" --numNodes "$N" --port "$PORT" \
    --numEpochs 2 "$@" &
done
python client_remote.py --nodeIndex 1 --numNodes "$N" --port "$PORT" \
  --numEpochs 2 "$@"
wait
