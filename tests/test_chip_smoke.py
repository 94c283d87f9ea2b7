"""Rehearsal of ``chip_smoke.py`` on the CPU at a tiny size.

The script refuses to run without a TPU; this test points its platform
check at the CPU and shrinks every phase, then runs its ``main`` as the
chip would: every phase, every check, and the last-line contract.
"""
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke as mod
    return mod


def test_chip_smoke_rehearsal_on_cpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "CASE1B", (300, 1, 10, 1, 1))
    monkeypatch.setattr(chip_smoke, "CASE2B", (20, 50, 1, 50, 50))
    monkeypatch.setattr(chip_smoke, "SOCKSHOP",
                        dict(n_clients=30, duration_s=40.0, tel_span_k=2))
    monkeypatch.setattr(chip_smoke, "SWEEP",
                        dict(points=8, loads=(10, 40), duration_s=20.0))
    chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    records = [json.loads(x) for x in lines]
    phases = [r["phase"] for r in records[:-1]]
    assert phases == ["a.case1b", "a.case1b/chip_vs_cpu", "b.sockshop",
                      "b.sockshop/chip_vs_cpu", "c.sweep", "d.case2b"]
    by_phase = dict(zip(phases, records))
    assert by_phase["a.case1b"]["deterministic"]
    assert by_phase["b.sockshop"]["traces_graph_checked"] > 0
    assert by_phase["c.sweep"]["point0_equals_solo"]
    for r in records[:-1]:
        if "wall_s" in r:
            assert r["platform"] == "cpu" and r["ticks"] > 0
            assert r["wall_s"] > 0
    assert records[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": records[0]["device_kind"], "count": 1}}


def test_chip_smoke_refuses_a_missing_tpu(chip_smoke, capsys):
    """Run as shipped on a CPU-only backend: fails at once, prints no
    result line and never carries on."""
    with pytest.raises(SystemExit, match="no tpu device"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""
