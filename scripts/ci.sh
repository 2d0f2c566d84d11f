#!/usr/bin/env bash
# One command to check the suite's green state.
#
#   scripts/ci.sh        -> lint, fast lane (-m "not slow"), then tier-1
#   scripts/ci.sh fast   -> lint + fast lane only
#
# The tier-1 command (ROADMAP.md): PYTHONPATH=src python -m pytest -x -q
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Lint first (config in pyproject.toml [tool.ruff]). The container image
# does not bake ruff in, so skip with a notice when it is unavailable
# rather than failing the whole lane.
echo "== lint: ruff check =="
if command -v ruff >/dev/null 2>&1; then
    ruff check src tests benchmarks examples scripts
elif python -m ruff --version >/dev/null 2>&1; then
    python -m ruff check src tests benchmarks examples scripts
else
    echo "ruff not installed; skipping lint (pip install ruff to enable)"
fi

echo "== fast lane: python -m pytest -q -m 'not slow' =="
python -m pytest -q -m "not slow"

echo "== paged-serving smoke: examples/serve_batched.py --engine paged =="
echo "   (includes the prefix smoke: shared system prompt must hit the"
echo "    prefix cache and pop strictly fewer pool blocks than cache-off)"
python examples/serve_batched.py --engine paged --prefix-cache

echo "== trace smoke: paged serve with --trace must emit a valid Perfetto trace =="
trace_out="$(mktemp /tmp/repro_trace.XXXXXX.json)"
python -m repro.launch.serve --arch yi-6b --reduced --batch 2 \
    --prompt-len 16 --gen 3 --engine paged --block-size 4 \
    --trace "$trace_out" >/dev/null
python - "$trace_out" <<'EOF'
import json, sys
path = sys.argv[1]
doc = json.load(open(path))  # raises on missing/invalid JSON
events = doc["traceEvents"]
assert events, f"{path}: traceEvents is empty"
names = {ev["name"] for ev in events}
for required in ("round", "decode_round", "decode_dispatch", "decode_wait"):
    assert required in names, f"{path}: missing '{required}' spans ({sorted(names)})"
print(f"ok: {len(events)} trace events ({len(names)} span kinds) in {path}")
EOF
rm -f "$trace_out"

echo "== metrics smoke: kernel_bench --json must embed the registry snapshot =="
bench_out="$(mktemp /tmp/repro_bench.XXXXXX.json)"
PYTHONPATH="$PYTHONPATH:." python -m benchmarks.kernel_bench --json > "$bench_out"
python - "$bench_out" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
assert "metrics" in rep and "autotune" in rep["metrics"], rep.keys()
kernels = rep["metrics"]["autotune"]["kernels"]
assert kernels, "kernel_bench run recorded no autotune samples"
with_bd = [k for k, v in rep["kernels"].items() if v.get("breakdown")]
assert with_bd, "no kernel produced a stall breakdown"
print(f"ok: metrics snapshot covers {len(kernels)} kernels; "
      f"breakdown on {sorted(with_bd)}")
EOF
rm -f "$bench_out"

echo "== chaos smoke: seeded fault schedule, every request must go terminal =="
python scripts/chaos_serve.py --seed 0 --rounds 50

echo "== guard smoke: kernel-site chaos under parity sentinels (ISSUE-10) =="
guard_out="$(mktemp /tmp/repro_guard.XXXXXX.json)"
REPRO_PARITY=sampled python scripts/chaos_serve.py --seed 3 --rounds 40 > "$guard_out"
python - "$guard_out" <<'EOF'
import json, sys
s = json.load(open(sys.argv[1]))
sub = s["substrate"]
by_site = s["faults"]["by_site"]
hits = sum(by_site.get(k, 0)
           for k in ("kernel_compile", "kernel_oom", "kernel_nan"))
assert hits > 0, f"chaos never hit a kernel site (pick a new seed): {by_site}"
assert sub["parity_mismatches"] == 0, sub
assert sub["injected_faults"] > 0, sub
print(f"ok: {hits} kernel-site faults absorbed, "
      f"{sub['parity_checks']} parity checks, 0 mismatches")
EOF
rm -f "$guard_out"

echo "== strict smoke: clean kernel bench under --strict must never degrade =="
strict_out="$(mktemp /tmp/repro_strict.XXXXXX.json)"
PYTHONPATH="$PYTHONPATH:." REPRO_PARITY=full \
    python -m benchmarks.kernel_bench --json --strict > "$strict_out"
python - "$strict_out" <<'EOF'
import json, sys
sub = json.load(open(sys.argv[1]))["substrate"]
assert sub["strict"], sub
assert sub["guarded_calls"] > 0, "bench made no guarded coro_calls"
assert sub["guarded_calls"] == sub["clean_calls"], sub
for k in ("backoffs", "fallbacks", "parity_mismatches", "breaker_trips"):
    assert sub[k] == 0, f"clean strict run degraded: {k}={sub[k]} ({sub})"
print(f"ok: {sub['guarded_calls']} guarded calls, all clean, "
      f"{sub['parity_checks']} full-parity checks under --strict")
EOF
rm -f "$strict_out"

echo "== machine smoke: far-memory profile must solve strictly deeper =="
near_json="$(python scripts/machine_smoke.py)"
far_json="$(REPRO_MACHINE=v5e-far-800ns python scripts/machine_smoke.py)"
echo "$near_json"
echo "$far_json"
python - "$near_json" "$far_json" <<'EOF'
import json, sys
near, far = (json.loads(a) for a in sys.argv[1:3])
assert near["machine"] == "v5e" and far["machine"] == "v5e-far-800ns", (near, far)
assert far["solved_depth"] > near["solved_depth"], (
    f"v5e-far-800ns depth {far['solved_depth']} must exceed "
    f"v5e depth {near['solved_depth']}")
print(f"ok: depth {near['solved_depth']} (v5e) -> "
      f"{far['solved_depth']} (v5e-far-800ns)")
EOF

if [[ "${1:-}" == "fast" ]]; then
    exit 0
fi

echo "== tier-1: python -m pytest -x -q =="
python -m pytest -x -q
