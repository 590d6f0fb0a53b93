"""Test harness: 8 virtual CPU devices so every collective path runs in CI
without hardware — the test story the reference lacks entirely (SURVEY.md §4:
no tests/ directory in the reference; its acceptance test was empirical
convergence curves, `Readme.md:283-294`).

Tests are hermetic and CPU-only, whatever accelerator the host has, so we
force the cpu platform and the virtual device count before any JAX
computation runs. XLA_FLAGS is read when the CPU client first
initializes, so setting it here is early enough.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import re  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
# The CLI mains place a persistent compile cache
# (runtime/platform.enable_compile_cache); tests neither read nor fill
# one, so every run compiles what it checks.
jax.config.update("jax_enable_compilation_cache", False)


# Tier-1 budget guard: experiment sweeps (experiments/) time whole training
# schedules and must only ever run under the `slow` marker. A test module
# that imports experiments/ without marking every one of its tests slow
# would silently blow the tier-1 window, so collection fails loudly.
_EXPERIMENTS_IMPORT = re.compile(
    r"^\s*(?:from|import)\s+experiments\b", re.MULTILINE
)


# Budget-rebalance convention (PR 4): a test demoted to `slow` must name
# its tier-1 twin in its docstring, so the default run's coverage story
# stays auditable. A parametrized sweep whose non-slow cases keep running
# in tier-1 is its own twin and needs no docstring note.
_TWIN_RE = re.compile(r"tier-?1|twin", re.IGNORECASE)


def pytest_collection_modifyitems(config, items):
    offenders = []
    checked = {}
    for item in items:
        path = str(getattr(item, "fspath", ""))
        if path not in checked:
            try:
                with open(path) as f:
                    checked[path] = bool(_EXPERIMENTS_IMPORT.search(f.read()))
            except OSError:
                checked[path] = False
        if checked[path] and item.get_closest_marker("slow") is None:
            offenders.append(item.nodeid)
    if offenders:
        raise pytest.UsageError(
            "tests importing experiments/ must be marked @pytest.mark.slow "
            "(tier-1 budget): " + ", ".join(sorted(offenders))
        )

    # Observability-name META-CHECK: every span/counter/metric name
    # emitted anywhere in the package (literal first argument to
    # .span/.counter/.instant/.complete/.observe/.inc/.gauge) must
    # appear in the documented registries
    # (observability/metrics.py: TRACE_EVENT_NAMES / METRIC_NAMES) —
    # an undocumented series is invisible to the exposition surface's
    # consumers. Pure source scan, no items
    # needed, so it runs on every collection; import is jax-free by
    # the metrics module's contract.
    from distributed_model_parallel_tpu.observability.metrics import (
        scan_emitted_names,
    )

    strays = scan_emitted_names()
    if strays:
        raise pytest.UsageError(
            "every emitted span/metric name must be documented in "
            "observability/metrics.py (TRACE_EVENT_NAMES / "
            "METRIC_NAMES): "
            + "; ".join(
                f"{name} at {', '.join(sites)}"
                for name, sites in sorted(strays.items())
            )
        )

    # slow-twin meta-check: group collected items by test function; a
    # function whose EVERY case is slow must document its tier-1 twin.
    # Only meaningful when whole files/dirs were collected: a direct
    # node-id invocation (re-running one CI failure) can select a lone
    # slow param of a mixed sweep, which would otherwise masquerade as
    # an undocumented all-slow function and abort collection.
    if any("::" in a for a in config.args):
        return
    by_fn = {}
    for item in items:
        key = (
            str(getattr(item, "fspath", "")),
            getattr(item, "originalname", item.name),
        )
        by_fn.setdefault(key, []).append(item)
    undocumented = []
    for (path, name), group in by_fn.items():
        if any(i.get_closest_marker("slow") is None for i in group):
            continue  # mixed sweep: the non-slow cases ARE the twin
        fn = getattr(group[0], "function", None)
        doc = getattr(fn, "__doc__", None) or ""
        if not _TWIN_RE.search(doc):
            undocumented.append(f"{path}::{name}")
    if undocumented:
        raise pytest.UsageError(
            "slow-demoted tests must name their tier-1 twin in their "
            "docstring (PR 4 budget-rebalance convention): "
            + ", ".join(sorted(undocumented))
        )

    # hlolint rule-coverage meta-check: every rule in the registry must
    # be exercised by at least one positive (violation detected) AND one
    # negative (clean) test, declared via @pytest.mark.hlo_rule(id,
    # polarity). A rule nobody can trip is a rule nobody can trust; a
    # rule with no clean case may be firing on everything. The registry
    # import is jax-free (analysis/rules.py module contract). Enforced
    # only on directory-style collection (the tier-1 gate's `pytest
    # tests/`) or when the rules module itself was collected — a
    # single-OTHER-file rerun must not fail for tests it never selected;
    # directory collection still catches a deleted/emptied rules module.
    import os

    dir_collection = any(
        os.path.isdir(a.split("::")[0]) for a in config.args
    )
    rules_collected = any(
        str(getattr(i, "fspath", "")).endswith("test_hlo_rules.py")
        for i in items
    )
    if not (dir_collection or rules_collected):
        return
    from distributed_model_parallel_tpu.analysis.rules import REGISTRY

    covered = {}
    for item in items:
        for m in item.iter_markers("hlo_rule"):
            if len(m.args) != 2:
                raise pytest.UsageError(
                    f"{item.nodeid}: hlo_rule marker takes exactly "
                    f"(rule_id, polarity) as positional args, got "
                    f"{m.args!r}"
                )
            rule_id, polarity = m.args
            if rule_id not in REGISTRY:
                raise pytest.UsageError(
                    f"{item.nodeid}: hlo_rule marker names unknown rule "
                    f"{rule_id!r} (registry: {sorted(REGISTRY)})"
                )
            if polarity not in ("positive", "negative"):
                raise pytest.UsageError(
                    f"{item.nodeid}: hlo_rule polarity must be "
                    f"'positive' or 'negative', got {polarity!r}"
                )
            covered.setdefault(rule_id, set()).add(polarity)
    missing = [
        f"{rid} (missing: "
        + ", ".join(sorted({"positive", "negative"} - covered.get(rid, set())))
        + ")"
        for rid in sorted(REGISTRY)
        if covered.get(rid, set()) != {"positive", "negative"}
    ]
    if missing:
        raise pytest.UsageError(
            "every hlolint rule needs one positive and one negative "
            "test (tag with @pytest.mark.hlo_rule(id, polarity), see "
            "tests/test_hlo_rules.py): " + "; ".join(missing)
        )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)
