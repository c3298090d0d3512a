"""The four-chip cell ``npb_mg_d.smooth_2x2`` resolves by name like the
one-chip cells (``test_bench_spec.py``), and reads the shard layer."""
import pytest

from bench.tests.test_bench_spec import test_cell_resolves_by_name as resolves


@pytest.mark.parametrize("workload", ["npb_mg_d.smooth_2x2"])
def test_cell_resolves_by_name(spec, workload):
    resolves(spec, workload)
    from bench import harness
    from bench.tests.conftest import ROOT

    cell = harness.resolve(spec, workload, ROOT)
    assert cell.chips == 4 and cell.config["mesh"] == [2, 2]
    assert "collective_ms" in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"sweep_ms", "setup_s"}
