"""The tier-1 suite's *registered* skips — the only ones allowed.

Every remaining skip in the suite is an optional-dependency gate, not a
disabled test: the six hypothesis properties have seeded deterministic
twins that always run (``*_deterministic``), and the two PuLP
cross-checks are redundant with the brute-force/reference cross-checks —
they only add the independent-CBC angle when ``pulp`` is installed (CI
installs both extras, so both gates are exercised there).  The two TPU
compile rehearsals skip where the installed jaxlib carries no TPU
compiler (a ``jax[cpu]`` install); with ``libtpu`` present they run.

``tools/check_skips.py`` audits the junitxml produced by ``make verify``
against this table and fails the build on any skip that is not listed
here with its exact reason; ``tests/test_skip_registry.py`` asserts the
table itself stays truthful (the nodeids exist and the gated reasons are
byte-exact).
"""

#: nodeid → tuple of acceptable reason prefixes.  A test may have more
#: than one (``test_dp_matches_pulp`` is double-gated: without hypothesis
#: the @given shim skips it first; with hypothesis but no pulp the
#: importorskip does).
REGISTERED_SKIPS = {
    "tests/test_ilp.py::test_dp_matches_brute_force":
        ("hypothesis not installed",),
    "tests/test_ilp.py::test_dp_matches_pulp":
        ("hypothesis not installed", "could not import 'pulp'"),
    "tests/test_ilp.py::test_alpha_zero_minimizes_cost":
        ("could not import 'pulp'",),
    "tests/test_solver_engine.py::test_engine_matches_pulp":
        ("could not import 'pulp'",),
    "tests/test_gss_efficiency.py::test_e_metrics_invariants":
        ("hypothesis not installed",),
    "tests/test_chaos.py::test_backoff_schedule_property":
        ("hypothesis not installed",),
    "tests/test_kernels.py::test_flash_ref_property":
        ("hypothesis not installed",),
    "tests/test_region.py::test_region_shock_purity_property":
        ("hypothesis not installed",),
    "tests/test_tpu_compile.py::test_prescan_compiles_for_v5e":
        ("no v5e:2x2 topology can be described here",),
    "tests/test_tpu_compile.py::test_golden_compiles_for_v5e":
        ("no v5e:2x2 topology can be described here",),
}

#: reason prefixes acceptable for *any* test: an install without jax
#: legitimately skips whole jax-native modules at collection time and
#: every @requires_jax test individually
ENVIRONMENT_REASON_PREFIXES = (
    "jax not installed",
    "could not import 'jax'",
)

__all__ = ["ENVIRONMENT_REASON_PREFIXES", "REGISTERED_SKIPS"]
