"""repro-lint CLI: run every invariant family against the repo tree.

Usage (CI runs this before the test matrix)::

    python -m repro.analysis.lint --baseline analysis_baseline.json

Exit status is non-zero on any finding not in the baseline (*new*
violations) **and** on any baseline entry no longer reproduced (*stale*
— the baseline must shrink with the fix, keeping the pass ratchet-only).
``--write-baseline`` regenerates the file; ``--json`` dumps findings for
tooling.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Callable, List, Sequence, Tuple

from repro.analysis import cache_keys, capabilities, kernel_shapes
from repro.analysis import oracle_parity
from repro.analysis.findings import (Finding, diff_baseline, load_baseline,
                                     sort_findings, to_json, write_baseline)


def default_root() -> Path:
    """Repo root, assuming the canonical src/repro/analysis layout."""
    return Path(__file__).resolve().parents[3]


def _run_cache_keys(root: Path) -> List[Finding]:
    findings = cache_keys.check_cache_keys(
        root / "src/repro/core/sweep.py",
        root / "src/repro/service/campaign.py",
        root / "src/repro/core/timing_model.py",
        root / "src/repro/core/engine_mix.py",
        repo_root=root)
    # The layout tuner keeps its own probe-score cache; its keys must
    # cover the same contention fields as the Sweep memo.
    findings.extend(cache_keys.check_sweep_cache_keys(
        root / "src/repro/core/autotune.py", repo_root=root,
        sweep_class="LayoutTuner", point_class="LayoutConfig"))
    return findings


def _run_oracle_parity(root: Path) -> List[Finding]:
    findings = oracle_parity.check_oracle_parity(
        root / "src/repro/core/timing_model.py",
        root / "src/repro/core/_timing_reference.py",
        root / "tests/core/test_timing_parity.py",
        repo_root=root)
    findings.extend(oracle_parity.check_jax_parity(
        root / "src/repro/core/timing_jax.py",
        root / "src/repro/core/timing_model.py",
        root / "tests/core/test_timing_differential.py",
        repo_root=root))
    findings.extend(oracle_parity.check_envelope_coverage(
        root / "src/repro/core/roofline_empirical.py",
        root / "tests/core/test_roofline_envelope.py",
        repo_root=root))
    return findings


def _run_capabilities(root: Path) -> List[Finding]:
    return capabilities.check_capability_contracts(
        sorted((root / "src/repro").rglob("*.py")), repo_root=root)


def _run_kernel_shapes(root: Path) -> List[Finding]:
    return kernel_shapes.check_kernel_safety(
        root / "src/repro/kernels/ops.py",
        experiments_path=root / "src/repro/core/experiments.py",
        repo_root=root)


FAMILIES: Tuple[Tuple[str, Callable[[Path], List[Finding]]], ...] = (
    ("cache_keys", _run_cache_keys),
    ("oracle_parity", _run_oracle_parity),
    ("capabilities", _run_capabilities),
    ("kernel_shapes", _run_kernel_shapes),
)


def run_analysis(root: Path) -> List[Finding]:
    """Every family over the real tree; fails loudly if the tree moved
    out from under the analyzer's configured paths."""
    required = (
        "src/repro/core/sweep.py",
        "src/repro/core/engine_mix.py",
        "src/repro/core/timing_model.py",
        "src/repro/core/timing_jax.py",
        "src/repro/core/_timing_reference.py",
        "src/repro/service/campaign.py",
        "src/repro/kernels/ops.py",
        "src/repro/core/autotune.py",
        "src/repro/core/roofline_empirical.py",
        "tests/core/test_timing_parity.py",
        "tests/core/test_timing_differential.py",
        "tests/core/test_roofline_envelope.py",
    )
    missing = [rel for rel in required if not (root / rel).exists()]
    if missing:
        raise FileNotFoundError(
            f"repro-lint: analyzed files missing under {root}: {missing} "
            f"(moved files must be re-pointed in repro.analysis.lint)")
    findings: List[Finding] = []
    for _, runner in FAMILIES:
        findings.extend(runner(root))
    return sort_findings(findings)


def main(argv: Sequence[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="AST-driven invariant analysis (DESIGN.md §11)")
    parser.add_argument("--root", type=Path, default=None,
                        help="repo root (default: inferred from layout)")
    parser.add_argument("--baseline", type=Path, default=None,
                        help="ratchet baseline JSON to compare against")
    parser.add_argument("--write-baseline", action="store_true",
                        help="rewrite the baseline from current findings")
    parser.add_argument("--json", type=Path, default=None,
                        help="dump full findings JSON to this path")
    args = parser.parse_args(argv)

    root = (args.root or default_root()).resolve()
    findings = run_analysis(root)

    if args.json is not None:
        args.json.write_text(json.dumps(to_json(findings), indent=2,
                                        sort_keys=True) + "\n")

    if args.write_baseline:
        if args.baseline is None:
            parser.error("--write-baseline requires --baseline")
        write_baseline(args.baseline, findings)
        print(f"repro-lint: wrote {len(findings)} finding(s) to "
              f"{args.baseline}")
        return 0

    if args.baseline is not None:
        diff = diff_baseline(findings, load_baseline(args.baseline))
        for f in diff.new:
            print(f.render())
        for key in diff.stale:
            print(f"{key[1]}: stale baseline entry {key[0]} "
                  f"({key[2]!r}) — the violation is fixed; remove it "
                  f"from {args.baseline}")
        status = "clean" if diff.clean else (
            f"{len(diff.new)} new, {len(diff.stale)} stale")
        print(f"repro-lint: {len(findings)} finding(s), baseline "
              f"{args.baseline}: {status}")
        return 0 if diff.clean else 1

    for f in findings:
        print(f.render())
    print(f"repro-lint: {len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
