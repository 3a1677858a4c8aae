"""Schedule optimizer entry points: the ``optimize=`` values the schedule
cache and the artifact store accept, and the selector's ``opt:``
candidates."""

import pytest

from repro.core import passes as P
from repro.core import schedule_ir as IR
from repro.core import selector
from repro.core.topology import Topology


# ---------------------------------------------------------------------------
# the optimize= values
# ---------------------------------------------------------------------------


def test_unknown_optimize_mode():
    """``"color"`` is the one optimizer pipeline: the retired mode names
    and anything else raise, in the cache and in the store's fingerprint
    check alike."""
    topo = Topology(2, 4, 2)
    for mode in ("nope", "lane", "ported", "reorder", "split"):
        with pytest.raises(ValueError, match="unknown optimize mode"):
            P.mode_fingerprint(mode, topo)
        with pytest.raises(ValueError, match="unknown optimize mode"):
            IR.compiled_schedule(
                "alltoall", "kported", topo, 2, 3, optimize=mode
            )


# ---------------------------------------------------------------------------
# selector integration: opt: candidates
# ---------------------------------------------------------------------------


def test_selector_offers_opt_candidates():
    algs = selector._candidate_algs("alltoall", Topology(2, 16, 8))
    assert "opt:klane" in algs and "opt:fulllane" in algs
    assert "klane" in algs


def test_select_ranks_opt_variants():
    ch = selector.select(
        "alltoall", 1 << 8, num_nodes=4, procs_per_node=16, k_lanes=4
    )
    names = [a for a, _ in ch.candidates]
    assert any(a.startswith("opt:") for a in names)
    # in this latency-regime cell every optimized variant prices at or
    # below its own base family
    d = dict(ch.candidates)
    for a, t in ch.candidates:
        if a.startswith("opt:") and a[4:] in d:
            assert t <= d[a[4:]] + 1e-9


def test_crossover_table_with_opt_candidates():
    sizes = [1 << 4, 1 << 12, 1 << 24]
    table = selector.crossover_table(
        "alltoall", sizes=sizes, num_nodes=4, procs_per_node=16, k_lanes=4
    )
    assert [s for s, _, _ in table] == sizes
    assert all(est > 0 for _, _, est in table)
