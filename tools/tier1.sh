#!/usr/bin/env bash
# Tier-1 verify — the test command the driver runs after every PR
# (`commands` in /root/TESTS_LAST_RUN.json: six xdist workers, tests
# dealt by file, 1,470 s). Checked in so builder and reviewer run the
# same line instead of copy-pasting divergent variants.
#
#   bash tools/tier1.sh            # from the repo root
#
# Behavior, matching the driver's line (the only additions are the two
# pre-gates and the --durations flags, which append a report section
# the pass count never sees):
#   * CPU-only jax (the conftest also forces it),
#   * the default marker filter (-m 'not slow', see pytest.ini),
#   * a fast `--collect-only` PRE-GATE so import/collection errors fail
#     in seconds with the module named (exit 2), instead of surfacing
#     mid-run; the main pass still carries
#     --continue-on-collection-errors as a belt-and-braces backstop,
#   * an `hlolint` PRE-GATE (tools/hlolint --pregate, exit 3): the
#     collective-contract linter over tinycnn DDP/FSDP overlapped plus
#     the tinycnn-sized hierarchical-MoE combo, so a broken
#     ring/fabric/overlap/dispatch contract fails in seconds with the
#     violated rule named (INTERNALS.md section 8b has the catalog),
#   * 1,470 s budget with a hard kill 10 s later,
#   * DOTS_PASSED=<n> as the driver counts it: from the junit file
#     (tests - errors - failures - skipped), else from the progress
#     dots of the tee'd log; WORKERS_DOWN=<n> beside it,
#   * a per-module slowest-10 durations digest (from pytest's
#     --durations section). Tests are dealt to workers by FILE, so the
#     longest file bounds the wall: a WARNING line names any module
#     whose >=0.5s tests total more than 240 s (split it, as
#     tests/test_plan_parity.py was split from tests/test_plan.py),
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

rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    --durations=0 --durations-min=0.5 \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile \
    --junitxml=/tmp/_t1.xml -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' \
    /tmp/_t1.xml 2>/dev/null | head -n 1 \
    | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' \
    /tmp/_t1.log | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log \
    2>/dev/null)

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
    if total > 240:
        print(f"[tier1-durations] WARNING: {mod} exceeds 240s "
              f"({total:.1f}s): one worker carries all of it (tests "
              f"are dealt by file), split the module")
PYEOF

exit $rc
