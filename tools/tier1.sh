#!/usr/bin/env bash
# Tier-1 verify — THE canonical test command (ROADMAP.md "Tier-1
# verify"). Checked in so builder and reviewer run the same line instead
# of copy-pasting divergent variants.
#
#   bash tools/tier1.sh            # from the repo root
#
# Behavior, matching the ROADMAP line (the only additions are the
# --durations flags, which append a report section pytest's dot
# protocol and our DOTS_PASSED grep never see):
#   * CPU-only jax (the conftest also forces it),
#   * the default marker filter (-m 'not slow', see pytest.ini) — the
#     full S×V×M pipeline-schedule parity sweep is `slow`; tier-1 keeps
#     its S=2,V=2,M=4 smoke case,
#   * a fast `--collect-only` PRE-GATE so import/collection errors fail
#     in seconds with the module named (exit 2), instead of surfacing
#     mid-run; the main pass still carries
#     --continue-on-collection-errors as a belt-and-braces backstop,
#   * an `hlolint` PRE-GATE (tools/hlolint --pregate, exit 3): the
#     collective-contract linter over tinycnn DDP/FSDP overlapped plus
#     the tinycnn-sized hierarchical-MoE combo, so a broken
#     ring/fabric/overlap/dispatch contract fails in seconds with the
#     violated rule named (INTERNALS.md section 8b has the catalog),
#   * costgate / obsreport / plangate PRE-GATES (exits 4/5/6): the
#     static cost ledger, the golden run report, and the auto-tuner's
#     committed plan grid, each failing with the combo/line/cell named,
#   * 870 s budget with a hard kill 10 s later,
#   * DOTS_PASSED=<n> printed from the progress dots as a
#     tamper-resistant pass count (parsed from the tee'd log, not from
#     pytest's summary line),
#   * a per-module slowest-10 durations digest (from pytest's
#     --durations section) so a module creeping toward the 870 s budget
#     is visible in every run, not just the ones that blow it, with an
#     explicit WARNING line for any module whose >=0.5s tests total
#     more than 120 s (the budget-rebalance trigger: such a module is
#     the next candidate for a slow demotion with a tier-1 twin),
#   * exits with pytest's status (PIPESTATUS survives the tee).

set -o pipefail
cd "$(dirname "$0")/.."

# Collection pre-gate: a broken import/collect error should fail the
# gate in SECONDS-not-minutes with the offending module named, instead
# of surfacing mid-run (or hiding behind
# --continue-on-collection-errors in the main pass). --collect-only
# runs no tests; the budget covers importing every test module on this
# 1-core host (~90 s, jax import dominates).
rm -f /tmp/_t1_collect.log
if ! timeout -k 5 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --collect-only \
    -p no:cacheprovider > /tmp/_t1_collect.log 2>&1; then
  echo "[tier1] COLLECTION FAILED — fix imports before the suite runs:"
  tail -40 /tmp/_t1_collect.log
  echo DOTS_PASSED=0
  exit 2
fi
echo "[tier1] collection ok:" \
  "$(grep -cE '::' /tmp/_t1_collect.log || true) tests collected"

# hlolint pre-gate (mirrors the --collect-only pre-gate): lint the
# deepest-rule-stack combos (tinycnn DDP + FSDP overlapped — rings,
# overlap deps, BN allowlist, at-rest sharding — plus the tinycnn-sized
# hierarchical-MoE dispatch combo, the tinycnn-sized quantized-dcn
# combo so a broken wire codec fails with dcn-compressed-payload
# named, and the speculative paged+ringed serve combo so a verify step
# that falls off the rings fails with spec-verify-step named) BEFORE
# the suite, so a broken collective contract fails in seconds with the
# violated rule NAMED instead of as a slow structural-test failure
# mid-run. Exit 3 distinguishes a contract violation from a collection
# failure (2).
rm -f /tmp/_t1_hlolint.log
if ! timeout -k 5 300 bash tools/hlolint --pregate \
    > /tmp/_t1_hlolint.log 2>&1; then
  echo "[tier1] HLOLINT PRE-GATE FAILED — a collective contract is" \
    "violated (tools/hlolint, INTERNALS.md section 8b):"
  grep -aE "ERROR|WARN|LOWERING FAILED|hlo_lint" /tmp/_t1_hlolint.log \
    | head -20
  echo DOTS_PASSED=0
  exit 3
fi
echo "[tier1] hlolint pre-gate ok:" \
  "$(grep -ac '"partial": true' /tmp/_t1_hlolint.log || true)" \
  "combo(s) lint clean"

# costgate pre-gate (the perf twin of the hlolint pre-gate): the
# static cost engine re-prices the tier-1 combo cut against the
# committed ledger (experiments/cost_ledger.json) and name-checks
# every full-matrix combo for ledger coverage — a combo whose
# predicted step time regressed past tolerance, or a new combo shipped
# without a cost baseline, fails in seconds with the combo NAMED.
# Exit 4 distinguishes a cost regression from a contract violation (3)
# and a collection failure (2).
rm -f /tmp/_t1_costgate.log
if ! timeout -k 5 300 bash tools/costgate --pregate \
    > /tmp/_t1_costgate.log 2>&1; then
  echo "[tier1] COSTGATE PRE-GATE FAILED — a combo's predicted step" \
    "time regressed or lacks a ledger row (tools/costgate," \
    "INTERNALS.md section 13):"
  grep -aE "FAIL|costgate" /tmp/_t1_costgate.log | head -20
  echo DOTS_PASSED=0
  exit 4
fi
echo "[tier1] costgate pre-gate ok:" \
  "$(grep -ac '"partial": true' /tmp/_t1_costgate.log || true)" \
  "combo(s) priced within tolerance"

# plangate pre-gate (the auto-tuner twin of the costgate pre-gate):
# re-run the deterministic knob search for the tier-1 cell cut
# (tinycnn DDP + the hierarchical-MoE cell) and compare argmin knobs +
# predicted step time against the committed
# experiments/tuned_plans.json, name-checking every grid cell — a
# drifted argmin (the cost landscape moved under an engine change) or
# a plan-less cell fails in seconds with the cell NAMED. Exit 6
# distinguishes a plan drift from a report regression (5), a cost
# regression (4), a contract violation (3) and a collection failure
# (2).
rm -f /tmp/_t1_plangate.log
if ! timeout -k 5 420 bash tools/plangate --pregate \
    > /tmp/_t1_plangate.log 2>&1; then
  echo "[tier1] PLANGATE PRE-GATE FAILED — a tuned plan's argmin or" \
    "predicted time drifted (tools/plangate, INTERNALS.md section 15):"
  grep -aE "FAIL|plangate" /tmp/_t1_plangate.log | head -20
  echo DOTS_PASSED=0
  exit 6
fi
echo "[tier1] plangate pre-gate ok:" \
  "$(grep -ac '"partial": true' /tmp/_t1_plangate.log || true)" \
  "cell(s) re-searched within tolerance"

# obsreport pre-gate (the measured twin of the costgate pre-gate):
# render the canned golden trace + metrics + ledger through the
# jax-free report pipeline (observability/report.py) and byte-compare
# against tests/golden/obsreport_report.txt — broken attribution /
# quantile / reconciliation semantics fail in under a second with the
# first diverging line printed. Exit 5 distinguishes a report
# regression from a cost regression (4), a contract violation (3) and
# a collection failure (2).
rm -f /tmp/_t1_obsreport.log
if ! timeout -k 5 60 bash tools/obsreport --pregate \
    > /tmp/_t1_obsreport.log 2>&1; then
  echo "[tier1] OBSREPORT PRE-GATE FAILED — the golden run report" \
    "drifted (tools/obsreport, INTERNALS.md section 14):"
  grep -aE "FAIL|obsreport|want:|got:" /tmp/_t1_obsreport.log | head -20
  echo DOTS_PASSED=0
  exit 5
fi
echo "[tier1] obsreport pre-gate ok:" \
  "$(grep -aco '"pregate": "ok"' /tmp/_t1_obsreport.log || true)" \
  "golden report byte-stable"

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    --durations=0 --durations-min=0.5 \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)

# Per-module slowest-10 digest from the durations section ("1.23s call
# tests/test_x.py::test_y" lines). Purely informational: never changes rc.
python - <<'PYEOF' || true
import collections
import re

rows = collections.defaultdict(list)
try:
    with open("/tmp/_t1.log") as f:
        for line in f:
            m = re.match(
                r"\s*([0-9.]+)s\s+call\s+(tests/[^:]+)::(\S+)", line
            )
            if m:
                rows[m.group(2)].append((float(m.group(1)), m.group(3)))
except OSError:
    rows = {}
for mod in sorted(rows, key=lambda k: -sum(s for s, _ in rows[k])):
    top = sorted(rows[mod], reverse=True)[:10]
    total = sum(s for s, _ in rows[mod])
    print(f"[tier1-durations] {mod} ({total:.1f}s in >=0.5s tests) "
          f"slowest-{len(top)}: "
          + ", ".join(f"{name}={secs:.1f}s" for secs, name in top))
    if total > 120:
        print(f"[tier1-durations] WARNING: {mod} exceeds 120s "
              f"({total:.1f}s) — candidate for a slow demotion with a "
              f"tier-1 twin (budget-rebalance convention)")
PYEOF

exit $rc
