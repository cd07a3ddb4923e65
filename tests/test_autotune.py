"""Autotuned partition configs: search space, content hash, on-disk cache.

The acceptance property lives here: the first admission of a matrix runs
the measured search (or the heuristic, when search is disabled) and writes
the winner to the cache; every later admission of the same content — same
registry, fresh registry, fresh process — skips the search and reuses the
cached config.
"""
import json

import pytest

from repro.core import PartitionConfig, enumerate_configs
from repro.core.matrices import circuit
from repro.core.tile import tuned_partition_config
from repro.serving import (
    AutotuneCache,
    MatrixRegistry,
    Probe,
    autotune_partition,
    cg_probe,
    matrix_hash,
    spmm_probe,
)
from repro.serving.autotune import device_kind

# tiny geometries keep each measured build/launch in the milliseconds
CANDIDATES = [
    PartitionConfig(row_block=64, col_block=128, group=8, lane=8),
    PartitionConfig(row_block=64, col_block=256, group=8, lane=16),
    PartitionConfig(row_block=128, col_block=128, group=8, lane=32),
]


@pytest.fixture()
def csr():
    return circuit(400, seed=2)


# --- search space ---------------------------------------------------------


def test_enumerate_configs_clips_and_dedups():
    cfgs = enumerate_configs((100, 200))
    assert cfgs, "search space must be non-empty"
    for cfg in cfgs:
        assert cfg.row_block <= 128  # next_pow2(100)
        assert cfg.col_block <= 256  # next_pow2(200)
        assert cfg.row_block % cfg.group == 0
    assert len({(c.row_block, c.col_block, c.group, c.lane) for c in cfgs}) == len(cfgs)
    # a big matrix keeps the nominal grid
    big = enumerate_configs((100_000, 100_000))
    assert any(c.row_block == 512 and c.col_block == 4096 for c in big)
    # group that divides no row_block -> empty, not an error
    assert enumerate_configs((64, 64), row_blocks=(64,), groups=(48,)) == []


# --- content hash ---------------------------------------------------------


def test_matrix_hash_is_content_addressed(csr):
    import copy

    assert matrix_hash(csr) == matrix_hash(copy.deepcopy(csr))
    other = circuit(400, seed=3)
    assert matrix_hash(csr) != matrix_hash(other)
    # value changes rehash, not just structure
    changed = copy.deepcopy(csr)
    changed.data = changed.data.copy()
    changed.data[0] += 1.0
    assert matrix_hash(csr) != matrix_hash(changed)


# --- measured search + cache ----------------------------------------------


def test_search_then_cache_round_trip(tmp_path, csr):
    cache = AutotuneCache(tmp_path / "cache")
    first = autotune_partition(csr, cache=cache, candidates=CANDIDATES, repeats=1)
    assert first.searched and not first.cache_hit
    assert first.evaluations == len(CANDIDATES)
    assert first.objective_us is not None and first.objective_us > 0
    assert first.cfg in CANDIDATES

    second = autotune_partition(csr, cache=cache, candidates=CANDIDATES, repeats=1)
    assert second.cache_hit and not second.searched
    assert second.evaluations == 0
    assert second.cfg == first.cfg
    # the persisted entry is plain JSON, keyed by the content hash
    entry = json.loads((tmp_path / "cache" / f"{matrix_hash(csr)}.json").read_text())
    assert PartitionConfig(**entry["config"]) == first.cfg


def test_search_disabled_falls_back_to_heuristic(tmp_path, csr):
    cache = AutotuneCache(tmp_path / "cache")
    res = autotune_partition(csr, cache=cache, search=False)
    assert not res.searched and not res.cache_hit and res.evaluations == 0
    assert res.cfg == tuned_partition_config(csr)
    # the heuristic result is cached like a searched one
    again = autotune_partition(csr, cache=cache, search=False)
    assert again.cache_hit and again.cfg == res.cfg


def test_search_upgrades_heuristic_cache_entry(tmp_path, csr):
    """A heuristic entry must not permanently satisfy search=True callers:
    the first measured admission upgrades it, after which both modes hit."""
    cache = AutotuneCache(tmp_path / "cache")
    heur = autotune_partition(csr, cache=cache, search=False)
    upgraded = autotune_partition(csr, cache=cache, candidates=CANDIDATES, repeats=1)
    assert upgraded.searched and not upgraded.cache_hit
    assert upgraded.evaluations == len(CANDIDATES)
    assert autotune_partition(csr, cache=cache, candidates=CANDIDATES).cache_hit
    # and the searched entry satisfies heuristic callers too
    res = autotune_partition(csr, cache=cache, search=False)
    assert res.cache_hit and res.cfg == upgraded.cfg
    del heur


def test_searched_entry_is_keyed_by_candidate_space(tmp_path, csr):
    """A search over a narrow candidate space must not satisfy a later
    admission searching a different space — it re-searches and overwrites."""
    cache = AutotuneCache(tmp_path / "cache")
    narrow = autotune_partition(csr, cache=cache, candidates=CANDIDATES[:1], repeats=1)
    assert narrow.searched
    full = autotune_partition(csr, cache=cache, candidates=CANDIDATES, repeats=1)
    assert full.searched and not full.cache_hit
    assert full.evaluations == len(CANDIDATES)
    # the full-space result now owns the entry
    assert autotune_partition(csr, cache=cache, candidates=CANDIDATES).cache_hit
    # zero-traffic matrices still hit for heuristic callers
    assert autotune_partition(csr, cache=cache, search=False).cache_hit


def test_corrupt_cache_entry_is_a_miss(tmp_path, csr):
    cache = AutotuneCache(tmp_path / "cache")
    autotune_partition(csr, cache=cache, search=False)
    entry = tmp_path / "cache" / f"{matrix_hash(csr)}.json"
    entry.write_text("{not json")
    res = autotune_partition(csr, cache=cache, search=False)
    assert not res.cache_hit  # recomputed, rewritten
    assert autotune_partition(csr, cache=cache, search=False).cache_hit


def test_foreign_device_entry_is_a_miss(tmp_path, csr):
    """An entry measured on another device kind never picks this one's
    geometry: it is a miss, and the re-measured entry replaces it."""
    cache = AutotuneCache(tmp_path / "cache")
    autotune_partition(csr, cache=cache, search=False)
    path = tmp_path / "cache" / f"{matrix_hash(csr)}.json"
    entry = json.loads(path.read_text())
    assert entry["device_kind"] == device_kind()
    entry["device_kind"] = "TPU v5 lite" if device_kind() == "cpu" else "cpu"
    entry["config"]["lane"] = 64  # a geometry only the foreign device chose
    path.write_text(json.dumps(entry))
    assert cache.get(matrix_hash(csr)) is None
    res = autotune_partition(csr, cache=cache, search=False)
    assert not res.cache_hit
    assert res.cfg == tuned_partition_config(csr)
    assert json.loads(path.read_text())["device_kind"] == device_kind()


def test_empty_candidates_uses_heuristic(tmp_path, csr):
    res = autotune_partition(
        csr, cache=AutotuneCache(tmp_path / "c"), candidates=[], repeats=1
    )
    assert not res.searched
    assert res.cfg == tuned_partition_config(csr)


# --- probe hook: solver-objective search -----------------------------------


def test_cg_probe_searches_and_caches(tmp_path, csr):
    """Time-to-tolerance ranking: a fixed-iteration CG run per candidate,
    cached like any measured search."""
    cache = AutotuneCache(tmp_path / "cache")
    probe = cg_probe(iters=3)
    res = autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1, probe=probe
    )
    assert res.searched and res.evaluations == len(CANDIDATES)
    assert res.objective_us is not None and res.objective_us > 0
    again = autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1, probe=probe
    )
    assert again.cache_hit and again.cfg == res.cfg


def test_probe_kind_fingerprints_cache_entries(tmp_path, csr):
    """Satellite acceptance: an entry searched under one objective must not
    satisfy an admission searching under another — the probe kind is part
    of the cache fingerprint."""
    cache = AutotuneCache(tmp_path / "cache")
    spmm_res = autotune_partition(csr, cache=cache, candidates=CANDIDATES, repeats=1)
    assert spmm_res.searched
    solver = autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1, probe=cg_probe(iters=3)
    )
    assert solver.searched and not solver.cache_hit  # spmm entry did not satisfy
    # the solver entry now owns the cache: solver callers hit, spmm re-search
    assert autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1, probe=cg_probe(iters=3)
    ).cache_hit
    assert autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1
    ).searched
    # distinct solver objectives are distinct kinds too
    assert cg_probe(iters=3).kind != cg_probe(iters=10).kind


def test_default_probe_keeps_historical_fingerprint(tmp_path, csr):
    """probe=None and probe=spmm_probe(...) with matching parameters are
    the same search — pre-probe cache entries stay warm."""
    cache = AutotuneCache(tmp_path / "cache")
    autotune_partition(csr, cache=cache, candidates=CANDIDATES, repeats=1)
    res = autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1,
        probe=spmm_probe(k=8, strategy="stable"),
    )
    assert res.cache_hit


def test_spmm_probe_params_fingerprint_cache_entries(tmp_path, csr):
    """An explicit spmm_probe with non-default k/strategy is a different
    objective from the default admission — its entry must not satisfy (or
    be satisfied by) a default-probe search."""
    cache = AutotuneCache(tmp_path / "cache")
    wide = autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1,
        probe=spmm_probe(k=16, strategy="reference"),
    )
    assert wide.searched
    default = autotune_partition(csr, cache=cache, candidates=CANDIDATES, repeats=1)
    assert default.searched and not default.cache_hit
    # and the default entry now hits only for the default objective
    assert autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1
    ).cache_hit
    assert autotune_partition(
        csr, cache=cache, candidates=CANDIDATES, repeats=1,
        probe=spmm_probe(k=16, strategy="reference"),
    ).searched


def test_custom_probe_object(tmp_path, csr):
    """Any (kind, measure) pair drives the search; the winner is whatever
    the objective says."""
    calls = []

    def measure(csr_, cfg, repeats):
        calls.append(cfg)
        return 1.0 if cfg is CANDIDATES[1] else 100.0

    res = autotune_partition(
        csr, cache=AutotuneCache(tmp_path / "c"), candidates=CANDIDATES,
        repeats=1, probe=Probe(kind="synthetic", measure=measure),
    )
    assert len(calls) == len(CANDIDATES)
    assert res.cfg == CANDIDATES[1]


def test_registry_passes_probe_through(tmp_path, csr):
    reg = MatrixRegistry(
        cache_dir=tmp_path / "cache", candidates=CANDIDATES,
        probe=cg_probe(iters=2),
    )
    plan = reg.admit(csr, "A")
    assert plan.autotune_searched
    # fresh registry with the same probe hits the same entry
    reg2 = MatrixRegistry(
        cache_dir=tmp_path / "cache", candidates=CANDIDATES,
        probe=cg_probe(iters=2),
    )
    assert reg2.admit(csr, "A").autotune_cache_hit


# --- registry integration (the acceptance criterion) ----------------------


def test_second_admit_skips_search_and_reuses_config(tmp_path, csr):
    cache_dir = tmp_path / "cache"
    reg1 = MatrixRegistry(cache_dir=cache_dir, candidates=CANDIDATES)
    plan1 = reg1.admit(csr, "A")
    assert plan1.autotune_searched and not plan1.autotune_cache_hit

    # same registry, same content: resident plan, nothing recomputed
    assert reg1.admit(csr) is plan1
    assert plan1.admissions == 2

    # fresh registry (fresh process in production), same cache dir: the
    # on-disk entry supplies the config, no measured search runs
    reg2 = MatrixRegistry(cache_dir=cache_dir, candidates=CANDIDATES)
    plan2 = reg2.admit(csr, "A")
    assert plan2.autotune_cache_hit and not plan2.autotune_searched
    assert plan2.cfg == plan1.cfg
    stats = reg2.stats()["A"]
    assert stats["autotune_cache_hit"] is True


def test_pinned_config_bypasses_autotune(tmp_path, csr):
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", candidates=CANDIDATES)
    plan = reg.admit(csr, "A", cfg=CANDIDATES[0])
    assert plan.cfg == CANDIDATES[0]
    assert not plan.autotune_searched and not plan.autotune_cache_hit
    assert not (tmp_path / "cache").exists()  # nothing was written
    # re-admitting resident content with the same pin is fine...
    assert reg.admit(csr, cfg=CANDIDATES[0]) is plan
    # ...but a conflicting pin must not be silently ignored
    with pytest.raises(ValueError, match="already resident"):
        reg.admit(csr, cfg=CANDIDATES[1])
