#!/usr/bin/env bash
# Failure-detection / preemption-recovery supervisor (SURVEY §5: the reference
# has none — a crashed torch.distributed.launch rank hangs the others at the
# next collective, BASELINE/train.sh:1). This wrapper restarts the trainer
# with --auto_resume until it exits cleanly or retries are exhausted; the
# restart command is identical to the start command because auto-resume picks
# up the latest checkpoint in --out.
#
# Every non-zero exit is appended to $OUT/restarts.log (timestamp, host,
# process index, rc, backoff, attempt, action) when an --out dir is present
# in the args — the post-mortem record of what the recovery chain actually
# did. On pods every host's supervisor appends to the SAME shared log; the
# host=/proc= fields keep the interleaved lines attributable.
#
# Pod runs additionally max-write the supervisor attempt number into the
# shared $OUT/generation file before each restart: all hosts of a restart
# wave converge on the same generation (two hosts observing G both write
# G+1), and the trainer's rendezvous retry (parallel/fleet.py) logs/paces
# against it so per-host backoff drift cannot make hosts miss each other's
# rendezvous window.
#
# Elastic pods (FLEET_ELASTIC=1): the trainer caches the lease-derived
# membership in $OUT/fleet/membership (one line: gen=G world=0,1). Before
# each restart this supervisor re-reads it and re-exports
# FLEET_PROCESS_ID/FLEET_NUM_PROCESSES as this host's rank/size in the
# re-formed world — respawning into the CURRENT membership instead of the
# frozen launch env. Every restarts.log line also records gen=/world= so
# the re-formation history (2 -> 1 -> 2 after a rejoin) reads off one
# shared log.
#
# Usage: MAX_RESTARTS=5 bash scripts/supervise.sh <workload> --out runs/x [flags...]
set -u
max=${MAX_RESTARTS:-5}
n=0

# find the --out value so restart events can be logged next to the run's
# checkpoints/records; no --out, no log (nowhere durable to put it)
out=""
prev=""
for a in "$@"; do
  [ "$prev" = "--out" ] && out="$a"
  prev="$a"
done

# process identity for shared (pod) restart logs: FLEET_HOST_ID is stable
# across elastic re-formations (ranks are not), falling back to
# FLEET_PROCESS_ID; single-host runs show proc=-
host=$(hostname 2>/dev/null || echo "?")
proc=${FLEET_HOST_ID:-${FLEET_PROCESS_ID:--}}

mem_fields() { # -> "gen=G world=0,1" from $OUT/fleet/membership, "-" absent
  g="-"; w="-"
  if [ -n "$out" ] && [ -f "$out/fleet/membership" ]; then
    line=$(head -n 1 "$out/fleet/membership" 2>/dev/null || echo "")
    case "$line" in gen=*)
      g=${line#gen=}; g=${g%% *}
      w=${line##*world=}; w=${w%% *}
    ;; esac
  fi
  echo "gen=$g world=$w"
}

log_event() { # $1=rc $2=backoff $3=action
  [ -n "$out" ] || return 0
  mkdir -p "$out" 2>/dev/null || return 0
  echo "$(date -Is) host=$host proc=$proc rc=$1 backoff=${2}s attempt=$n/$max $(mem_fields) action=$3" \
    >> "$out/restarts.log"
}

reexport_membership() { # respawn into the re-formed world (elastic pods)
  [ -n "${FLEET_ELASTIC:-}" ] && [ "${FLEET_ELASTIC:-0}" != "0" ] || return 0
  [ -n "$out" ] && [ -f "$out/fleet/membership" ] || return 0
  line=$(head -n 1 "$out/fleet/membership" 2>/dev/null || echo "")
  w=${line##*world=}; w=${w%% *}
  [ -n "$w" ] && [ "$w" != "$line" ] || return 0
  me=${FLEET_HOST_ID:-${FLEET_PROCESS_ID:-}}
  [ -n "$me" ] || return 0
  rank=0; size=0; found=""
  oldIFS=$IFS; IFS=','
  for h in $w; do
    [ "$h" = "$me" ] && { found=1; rank=$size; }
    size=$((size + 1))
  done
  IFS=$oldIFS
  # only members re-export: a recovered host NOT yet in the cached world
  # keeps its launch env and rejoins when the survivors re-form around it
  if [ -n "$found" ] && [ "$size" -gt 0 ]; then
    export FLEET_PROCESS_ID="$rank" FLEET_NUM_PROCESSES="$size"
  fi
  return 0
}

bump_generation() { # max-write our attempt number into $OUT/generation
  [ -n "$out" ] || return 0
  gf="$out/generation"
  cur=$(cat "$gf" 2>/dev/null || echo 0)
  case "$cur" in (''|*[!0-9]*) cur=0;; esac
  if [ "$n" -gt "$cur" ]; then
    tmp="$gf.tmp.$$"
    echo "$n" > "$tmp" 2>/dev/null && mv "$tmp" "$gf" 2>/dev/null
  fi
  return 0
}

while true; do
  python -m ddp_classification_pytorch_tpu.cli.train "$@" --auto_resume
  rc=$?
  # a clean exit is logged too: on elastic pods the world transitions
  # (2 -> 1 -> 2) are reconstructed from restarts.log, and the final
  # converged state must appear there, not just the failures
  [ "$rc" -eq 0 ] && { log_event 0 0 exit; exit 0; }
  # rc classification lives HERE, one level below any window scheduler:
  # 2 is deterministic (config/usage — the trainer maps its own config
  # validation to SystemExit(2), same code argparse uses) — restarting
  # replays the same failure; 8 is deterministic too (the non-finite step
  # sentinel: training diverged, every restart resumes the same weights
  # into the same divergence) — a hot-loop restart would burn the whole
  # retry budget replaying it; bare 1 is an UNHANDLED runtime exception
  # (transient XlaRuntimeError, in-process OOM, dataloader
  # IO) — retryable, but with a backoff so a crash loop doesn't spin;
  # 3 is "no backend" (a launcher's code for "no TPU found"), where an
  # immediate restart finds the same — back off long enough for an
  # outage to pass; 6 is "rendezvous failed"
  # (parallel/fleet.py: jax.distributed.initialize never completed within
  # its retry budget) — outage-shaped, the peers may simply not have
  # restarted yet, so it takes the SAME long backoff as rc 3; 9 is
  # "pod-inconsistent" (the resume digest agreement failed — usually
  # shared-filesystem staleness) — retryable with the runtime backoff,
  # the next consensus pass normally agrees. Everything else (7 mid-run
  # hang, kill signals) restarts fast and
  # auto-resumes from the newest checkpoint.
  case "$rc" in
    2)
      echo "[supervise] rc=$rc is deterministic (config/usage error);" \
           "not restarting" >&2
      log_event "$rc" 0 stop
      exit "$rc" ;;
    8)
      echo "[supervise] rc=$rc is deterministic (training diverged:" \
           "sentinel hit max_bad_steps consecutive non-finite steps);" \
           "not restarting" >&2
      log_event "$rc" 0 stop
      exit "$rc" ;;
    1) backoff=${RUNTIME_BACKOFF_S:-30} ;;
    3) backoff=${OUTAGE_BACKOFF_S:-300} ;;
    6) backoff=${OUTAGE_BACKOFF_S:-300} ;;
    9) backoff=${RUNTIME_BACKOFF_S:-30} ;;
    10) backoff=${OUTAGE_BACKOFF_S:-300} ;;
    11) backoff=${REFORM_BACKOFF_S:-2} ;;
    *) backoff=2 ;;
  esac
  # 10 is "pod-unviable" (parallel/fleet.py: the survivor set is below
  # FLEET_MIN_PROCESSES or cannot cover the mesh) — outage-shaped like
  # rc 3/6, the dead peers may come back, so the long backoff; 11 is
  # "pod-reform" (membership changed at the epoch boundary) — every host
  # exits together ON PURPOSE, so restart fast into the re-formed world.
  n=$((n + 1))
  if [ "$n" -gt "$max" ]; then
    echo "[supervise] giving up after $n failures (last rc=$rc)" >&2
    log_event "$rc" "$backoff" give-up
    exit "$rc"
  fi
  echo "[supervise] trainer exited rc=$rc; restart $n/$max (auto-resume," \
       "${backoff}s backoff)" >&2
  log_event "$rc" "$backoff" restart
  bump_generation
  reexport_membership
  sleep "$backoff"
done
