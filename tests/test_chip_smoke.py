"""chip_smoke.py's own checks, run off the chip at small shapes: the
kernel parity phase (interpret-mode kernels against their references) and
the rule that the load size is fixed outside rehearsals."""

import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "KERNEL_NQ", 8)
    monkeypatch.setattr(mod, "KERNEL_C", 128)
    monkeypatch.setattr(mod, "KERNEL_ROWS", 256)
    return mod


def test_items_needs_rehearse(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main(["--items", "4096"])
    assert exc.value.code == 2
    assert "--rehearse" in capsys.readouterr().err


def test_kernel_parity_passes_in_interpret_mode(smoke, monkeypatch, capsys):
    monkeypatch.setenv("REPRO_QUERY_BACKEND", "interpret")
    smoke.kernel_parity(np.random.default_rng(0))
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "ids_equal_where_distinct=True" in ln]
    assert len(lines) == 6          # fp32, bf16, int8 x p = 1, 2


def test_kernel_parity_catches_a_wrong_id(smoke, monkeypatch):
    from repro.kernels import ops, ref

    def swapped(q, db, ids, k, p=2.0, valid_items=None):
        d, i = ref.fused_query_topk_ref(q, db, ids, k, p, valid_items)
        return d, i.at[:, [0, 1]].set(i[:, [1, 0]])

    monkeypatch.setattr(ops, "fused_query_topk", swapped)
    with pytest.raises(smoke.SmokeFailure, match="fp32 p=1"):
        smoke.kernel_parity(np.random.default_rng(0))
