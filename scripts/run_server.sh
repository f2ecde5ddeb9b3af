#!/usr/bin/env bash
# Opinionated production runner for the autotune server (DESIGN.md §12).
#
# Pins the environment the serving stack is tuned for, then execs the
# given entry point (default: examples/serve_http.py). Every knob is an
# override-able default — anything already set in the environment wins.
#
#   scripts/run_server.sh                         # HTTP front door demo
#   scripts/run_server.sh examples/serve_autotune.py
#   REPRO_SOLVE_EXECUTOR=sharded scripts/run_server.sh my_server.py
#
# Knobs (defaults below, see DESIGN.md for the sections that own them):
#   JAX_COMPILATION_CACHE_DIR  persistent XLA compile cache (§12):
#                            restarts rebuild the executable grid from
#                            disk with zero fresh compiles. Unset: the
#                            server keeps it at .cache/xla under the repo
#                            root.
#   REPRO_SOLVE_EXECUTOR     solve executor registry name (§7):
#                            local | sharded (a mesh over every device
#                            the host exposes). Default: local.
#   REPRO_PRECISION_BACKEND  precision backend registry name (§6):
#                            jnp | pallas | ... Default: the platform's
#                            (compiled pallas on a TPU, jnp elsewhere).
#   JAX_ENABLE_X64           the solvers' fp64 carrier on CPU hosts (§2).
#                            Pinned on — the bit-parity contract assumes
#                            it. A TPU serves the f32 carrier.
set -euo pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"

# --- allocator: tcmalloc when present (long-lived servers fragment the
# glibc heap under the batcher's steady large-array churn) --------------
if [[ -z "${LD_PRELOAD:-}" ]]; then
    for so in /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
              /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
              /usr/lib/libtcmalloc_minimal.so.4; do
        if [[ -e "$so" ]]; then
            export LD_PRELOAD="$so"
            # Silence the one-line report tcmalloc emits per large
            # (>1GiB) allocation — stacked solver batches trip it.
            export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-1099511627776}"
            break
        fi
    done
fi

# --- dtype + logging pins ---------------------------------------------
# fp64 carrier on (DESIGN.md §2); absl/XLA chatter off the serving logs.
export JAX_ENABLE_X64="${JAX_ENABLE_X64:-1}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# --- executor / backend selection (DESIGN.md §6-§7) --------------------
export REPRO_SOLVE_EXECUTOR="${REPRO_SOLVE_EXECUTOR:-local}"

export PYTHONPATH="$REPO_ROOT/src${PYTHONPATH:+:$PYTHONPATH}"

ENTRY="${1:-$REPO_ROOT/examples/serve_http.py}"
shift || true
exec python "$ENTRY" "$@"
