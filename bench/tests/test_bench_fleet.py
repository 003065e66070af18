"""A fleet cell needs only files: a traffic mix with ``replicas``,
``routing`` and ``shared_prefixes`` makes the harness serve through
``FleetEngine.build(..., routing="prefix")`` and still check every
sampled request against the reference (CPU, tiny sizes; the replicas
share the one CPU device)."""

import json
from pathlib import Path

import harness
from repro.fleet import FleetEngine

DATA = Path(__file__).resolve().parent / "data"


def test_fleet_cell_from_a_traffic_file(tmp_path, monkeypatch):
    spec = {
        "configs": [{"name": "tiny", "file": str(DATA / "tiny.json")}],
        "workloads": [{"name": "tiny.fleet", "config": "tiny", "traffic": "tiny_fleet", "chips": 1}],
        "end_to_end": [{"name": "itl_p95_ms", "unit": "ms"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    built = []
    build = FleetEngine.build.__func__

    def spy(cls, *args, **kw):
        fleet = build(cls, *args, **kw)
        built.append(fleet)
        return fleet

    monkeypatch.setattr(FleetEngine, "build", classmethod(spy))
    result = harness.run(
        "tiny.fleet", 9, 2.0, False, require_chip=False, root=tmp_path, traffic_dir=DATA
    )
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] == 24
    (fleet,) = built
    stats = fleet.stats()
    assert stats.n_replicas == 2 and stats.routing == "prefix"
    assert stats.prefix_hits > 0 and stats.grafted_tokens > 0
