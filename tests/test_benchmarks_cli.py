"""`python -m benchmarks.run`: the experiment registry's command line."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402
from repro.core.experiments import (CATALOG_BEGIN, CATALOG_END,  # noqa: E402
                                    all_experiments, catalog_markdown)


def test_unknown_experiment_exits_listing_the_registered_ones():
    with pytest.raises(SystemExit) as exc:
        run.main(["--experiments", "fig6_address_mapping,no_such_experiment"])
    msg = str(exc.value.code)
    assert "no_such_experiment" in msg
    for exp in all_experiments():
        assert exp.name in msg


@pytest.mark.parametrize("text, want", [
    ("16", 16),
    ("1", 1),
    ("2r+1w+1d", "2r+1w+1d"),
])
def test_engines_takes_a_count_or_a_mix(text, want):
    assert run.parse_engines_arg(text) == want


@pytest.mark.parametrize("text", ["0", "garbage", "2x+1w", "-1", "r+"])
def test_engines_rejects_anything_else(text):
    with pytest.raises(SystemExit) as exc:
        run.parse_engines_arg(text)
    assert "--engines" in str(exc.value.code)


def test_engine_ladder_is_powers_of_two_up_to_n():
    assert run.engine_ladder(16) == (1, 2, 4, 8, 16)
    assert run.engine_ladder(6) == (1, 2, 4, 6)
    assert run.engine_ladder(1) == (1,)


def test_catalog_splices_between_the_markers(tmp_path, capsys):
    target = tmp_path / "doc.md"
    target.write_text(f"# head\n{CATALOG_BEGIN}\nstale\n{CATALOG_END}\ntail\n")
    run.emit_catalog(str(target))
    assert target.read_text() == f"# head\n{catalog_markdown()}\ntail\n"
    assert "updated experiment catalog" in capsys.readouterr().out
    run.emit_catalog(str(target))          # idempotent
    assert target.read_text() == f"# head\n{catalog_markdown()}\ntail\n"


def test_catalog_refuses_a_file_without_markers(tmp_path):
    target = tmp_path / "doc.md"
    target.write_text("# no markers here\n")
    with pytest.raises(SystemExit) as exc:
        run.emit_catalog(str(target))
    assert "markers" in str(exc.value.code)
    assert target.read_text() == "# no markers here\n"


def test_quick_run_prints_the_header_and_one_row_per_spec(capsys):
    run.main(["--quick", "--experiments", "fig6_address_mapping"])
    lines = capsys.readouterr().out.splitlines()
    assert lines[0] == "name,us_per_call,derived"
    names = [line.split(",")[0] for line in lines[1:]]
    assert names == ["fig6_address_mapping_hbm", "fig6_address_mapping_ddr4"]
    derived = [line.split(",", 2)[2] for line in lines[1:]]
    assert derived == ["default_seq_gbps=6.64;policies=5",
                       "default_seq_gbps=18.06;policies=4"]
    assert all(float(line.split(",")[1]) > 0 for line in lines[1:])


@pytest.mark.parametrize("argv", [
    ["--grid"], ["--service"], ["--roofline"], ["--tune"], ["--lint-report"],
    ["--json", "out.json"], ["--fault-rate", "0.1"], ["--qps-target", "1"],
])
def test_a_removed_or_unknown_flag_is_an_error(argv, capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(argv)
    assert exc.value.code == 2
    captured = capsys.readouterr()
    assert "unrecognized arguments" in captured.err
    assert captured.out == ""


def test_burst_needs_burst_arbitration(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--burst", "8"])
    assert exc.value.code == 2
    assert "--arbitration burst" in capsys.readouterr().err
