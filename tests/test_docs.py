"""Documentation health: the CI docs job's link check, runnable in tier-1.

The docs job also executes examples/quickstart.py end to end; that is
deliberately CI-only (it builds a 2048-item index), but the link check is
cheap enough to gate every local run too.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_intra_repo_doc_links_resolve():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_doc_links.py"),
         ROOT], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    # the checker actually saw the doc tree (README, docs/, EXPERIMENTS...)
    assert "checked" in out.stdout
    n_files = int(out.stdout.split("checked ")[1].split()[0])
    assert n_files >= 5, out.stdout


def test_retired_knobs_may_be_named_but_not_read(tmp_path):
    """A knob named only in a doc is stale unless the retired list names
    it; a retired knob the code reads again fails."""
    for sub in ("src", "docs"):
        (tmp_path / sub).mkdir()
    (tmp_path / "src" / "knobs.py").write_text('READ = "REPRO_LIVE"\n')
    (tmp_path / "docs" / "retired-knobs.md").write_text("`REPRO_GONE`\n")
    (tmp_path / "NOTES.md").write_text("Removed `REPRO_GONE`.\n")
    (tmp_path / "docs" / "a.md").write_text("Set `REPRO_LIVE`.\n")
    checker = [sys.executable, os.path.join(ROOT, "tools",
                                            "check_doc_links.py"),
               str(tmp_path)]

    def run():
        return subprocess.run(checker, capture_output=True, text=True,
                              timeout=60)

    out = run()
    assert out.returncode == 0, out.stdout + out.stderr
    (tmp_path / "docs" / "b.md").write_text("Set `REPRO_OTHER`.\n")
    out = run()
    assert out.returncode == 1
    assert "STALE-KNOB docs/b.md:1: REPRO_OTHER" in out.stdout
    (tmp_path / "docs" / "b.md").unlink()
    (tmp_path / "src" / "back.py").write_text('AGAIN = "REPRO_GONE"\n')
    out = run()
    assert out.returncode == 1
    assert "RETIRED-KNOB REPRO_GONE" in out.stdout
