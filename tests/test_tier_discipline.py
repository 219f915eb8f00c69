"""Tier-marker discipline guard (default tier, on purpose).

The suite's 5-minute default tier is defined NEGATIVELY — unmarked tests
— so a new test module added without a tier decision silently lands
there and bloats the tier everyone runs (tests/README.md).  This guard
makes the decision explicit: every `tests/test_*.py` module must either
carry a module-level `pytestmark` naming a recognized tier
(slow / kernels / serving) or be listed in the DEFAULT_TIER ledger
below, which records that its author CHOSE the default tier.

The check is static (file text, no imports) so it costs milliseconds
and cannot be skipped by collection errors in the offending module.
"""
import pathlib
import re

TIER_MARKS = ("slow", "kernels", "serving")

# Deliberate default-tier membership.  Adding a module here is a
# statement that its tests belong in the <=5-minute tier — keep it fast.
DEFAULT_TIER = {
    "test_accelerator.py",
    "test_activation_checkpointing.py",
    "test_analysis.py",
    "test_autotp_linear.py",
    "test_aux.py",
    "test_cli_tools.py",
    "test_compression.py",
    "test_config.py",
    "test_chip_smoke.py",
    "test_data_pipeline.py",
    "test_device.py",
    "test_docs.py",
    "test_domino_zenflow.py",
    "test_engine.py",
    "test_hpz_mics.py",
    "test_indexed_dataset.py",
    "test_launcher_tuner.py",
    "test_mesh_comm.py",
    "test_moe_gating.py",
    "test_moq_eigenvalue.py",
    "test_native_ops.py",
    "test_pipe_module.py",
    "test_quantization.py",
    "test_tier_discipline.py",
    "test_zero_init_api.py",
}

_PYTESTMARK_RE = re.compile(
    r"^pytestmark\s*=.*pytest\.mark\.(" + "|".join(TIER_MARKS) + r")\b",
    re.MULTILINE)


def test_every_test_module_has_an_explicit_tier():
    tests_dir = pathlib.Path(__file__).parent
    offenders = []
    for path in sorted(tests_dir.glob("test_*.py")):
        if path.name in DEFAULT_TIER:
            continue
        if _PYTESTMARK_RE.search(path.read_text()):
            continue
        offenders.append(path.name)
    assert not offenders, (
        f"test modules without a tier decision: {offenders}.  Either add "
        f"`pytestmark = pytest.mark.<{'|'.join(TIER_MARKS)}>` (module "
        f"level) or, if the tests really belong in the 5-minute default "
        f"tier, add the filename to DEFAULT_TIER in "
        f"tests/test_tier_discipline.py — the default tier only grows "
        f"deliberately."
    )


def test_default_tier_ledger_has_no_stale_entries():
    """A ledger entry for a deleted or since-marked module is noise that
    weakens the guard — prune it."""
    tests_dir = pathlib.Path(__file__).parent
    stale = []
    for name in sorted(DEFAULT_TIER):
        path = tests_dir / name
        if not path.exists():
            stale.append(f"{name} (file gone)")
        elif _PYTESTMARK_RE.search(path.read_text()):
            stale.append(f"{name} (now tier-marked)")
    assert not stale, f"prune stale DEFAULT_TIER entries: {stale}"
