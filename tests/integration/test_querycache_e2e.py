"""End-to-end query cache: a warm re-analysis against a shared disk store
must serve from the cache (nonzero hit-rate) and produce the identical
issue set — the acceptance criterion for cached-verdict soundness."""

from bench_contracts import check_recall, run_analysis
from mythril_tpu.observability import get_registry, observability_meta
from mythril_tpu.querycache import configure, get_query_cache, \
    reset_query_cache


def _issue_keys(issues):
    return sorted((i.swc_id, i.address) for i in issues)


def query_cache_compare(cache_dir) -> dict:
    """Warm-vs-cold query-cache comparison on the killbilly workload.

    Runs the analysis twice against one disk-backed cache directory: the
    cold run populates the store, the warm run (fresh in-process cache via
    ``clear_caches``) must hit it.  Asserts a nonzero warm hit count and
    an issue set identical to the cold run, then returns the hit counters
    and both runs' ``querycache`` stats.
    """
    try:
        configure(enabled=True, cache_dir=str(cache_dir))

        get_registry().reset(prefix="querycache.")
        _, cold_issues, _ = run_analysis("host")
        cold_stats = dict(get_query_cache().stats())

        get_registry().reset(prefix="querycache.")
        _, warm_issues, _ = run_analysis("host")
        warm_stats = dict(get_query_cache().stats())
        warm_hits = get_query_cache().hits_total()

        assert warm_hits > 0, f"warm run had zero cache hits: {warm_stats}"
        assert _issue_keys(cold_issues) == _issue_keys(warm_issues)
        lookups = warm_stats.get("lookups", 0)
        return {
            "warm_hits": warm_hits,
            "warm_hit_rate": round(warm_hits / lookups, 4) if lookups else 0.0,
            "issues": _issue_keys(cold_issues),
            "cold": cold_stats,
            "warm": warm_stats,
        }
    finally:
        configure(enabled=True, cache_dir=None)


def test_warm_run_hits_and_matches_cold_issue_set(tmp_path):
    try:
        configure(enabled=True, cache_dir=str(tmp_path))

        get_registry().reset(prefix="querycache.")
        _, cold_issues, _ = run_analysis("host")
        check_recall(cold_issues)
        cold_stats = get_query_cache().stats()
        assert cold_stats["stores"] > 0, "cold run recorded nothing"

        # run_analysis -> clear_caches drops the in-process layer, so the
        # warm run's exact hits can only come through the disk store
        get_registry().reset(prefix="querycache.")
        _, warm_issues, _ = run_analysis("host")
        check_recall(warm_issues)

        warm_hits = get_query_cache().hits_total()
        warm_stats = get_query_cache().stats()
        assert warm_hits > 0, f"warm run had zero cache hits: {warm_stats}"
        assert warm_stats["disk_reads"] > 0, \
            f"warm hits bypassed the disk store: {warm_stats}"
        assert _issue_keys(cold_issues) == _issue_keys(warm_issues)

        # the hit counters must surface in report meta via observability
        meta = observability_meta()
        assert meta["metrics"]["querycache.lookups"] > 0
        assert sum(
            meta["metrics"][k]
            for k in (
                "querycache.exact_hits",
                "querycache.model_hits",
                "querycache.core_hits",
                "querycache.unknown_hits",
            )
        ) == warm_hits
    finally:
        configure(enabled=True, cache_dir=None)
        reset_query_cache()


def test_query_cache_compare_mode(tmp_path):
    """The warm-vs-cold comparison (asserts internally; shape-checked
    here)."""
    out = query_cache_compare(tmp_path)
    assert out["warm_hits"] > 0
    assert 0 < out["warm_hit_rate"] <= 1
    assert out["issues"], "killbilly exploit missing from compare mode"
    assert out["cold"]["stores"] > 0
