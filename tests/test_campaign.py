"""Campaign orchestrator: manifest grammar, crash-safe store semantics,
resume/shard partitioning, quarantine isolation, and the store-only
report layer.  The centerpiece is the crash-restart drill: a campaign
killed after N cells and resumed must produce a ``cells/`` tree
bit-identical to an uninterrupted run, with no cell executed twice."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.bench import report as bench_report
from repro.bench import results
from repro.campaign import (CampaignStore, Cell, Dataset, Grid, Manifest,
                            cell_key, dataset_winners, load_manifest,
                            pending_cells, plan_cells, render_report,
                            run_campaign, scan_corpus, shard_cells)
from repro.campaign.report import campaign_records, format_report
from repro.data import ingest

ROOT = pathlib.Path(__file__).resolve().parent.parent
CORPUS = ROOT / "benchmarks" / "corpus"

GRID = Grid(policies=("fifo", "lru"), K=(64,), seeds=(0,), T=2000)


@pytest.fixture(scope="module")
def corpus_manifest():
    return scan_corpus(str(CORPUS), name="mini", grid=GRID)


@pytest.fixture(scope="module")
def full_store(tmp_path_factory, corpus_manifest):
    """One uninterrupted run over the committed corpus — the reference
    store for the bit-identity drill and the report tests."""
    store = CampaignStore(str(tmp_path_factory.mktemp("full") / "store"))
    summary = run_campaign(corpus_manifest, store)
    assert summary.counts["quarantined"] == 0
    assert summary.counts["remaining"] == 0
    return store


# --- manifest grammar -------------------------------------------------------

def test_grid_validation():
    with pytest.raises(ValueError, match="at least one policy"):
        Grid(policies=())
    with pytest.raises(ValueError, match="regime letters"):
        Grid(policies=("lru",), K=("M",))
    with pytest.raises(ValueError, match="positive cap"):
        Grid(policies=("lru",), T=0)
    # ints and regime letters coexist, coerced
    g = Grid(policies=("lru",), K=("S", "64"), seeds=("3",))
    assert g.K == ("S", 64) and g.seeds == (3,)


def test_manifest_roundtrip_and_validation():
    m = Manifest(name="demo", root=".", grid=Grid(policies=("lru",)),
                 datasets=(Dataset(name="d", glob="*.csv"),))
    assert Manifest.from_dict(m.to_dict()) == m
    with pytest.raises(ValueError, match="schema"):
        Manifest.from_dict(dict(m.to_dict(), schema="nope/v9"))
    with pytest.raises(ValueError, match="unique"):
        Manifest(name="demo", root=".", grid=Grid(policies=("lru",)),
                 datasets=(Dataset(name="d", glob="*.csv"),
                           Dataset(name="d", glob="*.txt")))
    with pytest.raises(ValueError, match="glob.*or.*traces"):
        Dataset(name="empty")


def test_manifest_empty_glob_is_an_error(tmp_path):
    m = Manifest(name="demo", root=str(tmp_path),
                 grid=Grid(policies=("lru",)),
                 datasets=(Dataset(name="d", glob="*.nothere"),))
    with pytest.raises(ValueError, match="matched no trace files"):
        m.traces()


def test_load_manifest_reanchors_relative_root(tmp_path):
    traces = tmp_path / "traces"
    traces.mkdir()
    ingest.write_csv(str(traces / "a.csv"), [1, 2, 1], [10, 20, 10])
    m = Manifest(name="demo", root="traces",
                 grid=Grid(policies=("lru",)),
                 datasets=(Dataset(name="d", glob="*.csv"),))
    m.save(str(tmp_path / "campaign.json"))
    loaded = load_manifest(str(tmp_path / "campaign.json"))
    assert os.path.isabs(loaded.root)
    [(ds, path, fmt)] = loaded.traces()
    assert (ds, os.path.basename(path)) == ("d", "a.csv")


def test_scan_corpus_groups_and_freezes_stats(corpus_manifest):
    names = {d.name for d in corpus_manifest.datasets}
    assert names == {"csv", "oracle", "txt"}       # grouped by format
    all_traces = [p for d in corpus_manifest.datasets for p, _ in d.traces]
    # the plain .bin with a committed .gz twin is skipped, not duplicated
    assert not any(p.endswith(".oracleGeneral.bin") for p in all_traces)
    for d in corpus_manifest.datasets:
        for rel, _ in d.traces:
            assert d.stats[rel]["n_requests"] > 0   # frozen characterization


# --- store ------------------------------------------------------------------

def _tiny_payload(wall=1.5):
    return results.build_payload(
        "cell", config={}, wall_s=wall, schema=results.SCHEMA_V2,
        records=[{"metrics": {"miss_ratio": [0.5]}, "seeds": [0],
                  "wall_s": 0.7}])


def test_store_put_normalizes_and_get_revalidates(tmp_path):
    store = CampaignStore(str(tmp_path / "s"))
    path = store.put("aaaa", _tiny_payload())
    on_disk = json.load(open(path))
    assert on_disk["created_unix"] == 0.0 and on_disk["wall_s"] == 0.0
    assert on_disk["records"][0]["wall_s"] == 0.0
    assert store.get("aaaa")["schema"] == results.SCHEMA_V2
    assert store.completed() == ["aaaa"]
    # volatile fields zeroed identically regardless of actual timings
    store.put("bbbb", _tiny_payload(wall=99.0))
    a, b = (open(store.path_for(k)).read() for k in ("aaaa", "bbbb"))
    assert a == b
    assert not [f for f in os.listdir(store.cells_dir) if ".tmp." in f]


def test_store_rejects_invalid_payloads(tmp_path):
    store = CampaignStore(str(tmp_path / "s"))
    with pytest.raises(ValueError, match="schema"):
        store.put("aaaa", {"schema": "bogus"})
    assert not store.has("aaaa")                   # nothing landed


def test_store_pins_manifest_and_rejects_mismatch(tmp_path):
    store = CampaignStore(str(tmp_path / "s"))
    m1 = Manifest(name="a", root=".", grid=Grid(policies=("lru",)),
                  datasets=(Dataset(name="d", glob="*.csv"),))
    store.init_manifest(m1)
    store.init_manifest(m1)                        # idempotent
    m2 = dataclasses.replace(m1, grid=Grid(policies=("fifo",)))
    with pytest.raises(ValueError, match="different.*manifest"):
        store.init_manifest(m2)


# --- planning, sharding, resume --------------------------------------------

def test_shards_partition_the_plan(corpus_manifest):
    cells = plan_cells(corpus_manifest)
    assert len(cells) == len({cell_key(c) for c in cells}) == 6
    shards = [shard_cells(cells, f"{i}/3") for i in range(3)]
    keys = [{cell_key(c) for c in s} for s in shards]
    assert set.union(*keys) == {cell_key(c) for c in cells}
    for i in range(3):
        for j in range(i + 1, 3):
            assert keys[i].isdisjoint(keys[j])


def test_crash_restart_is_bit_identical(tmp_path, corpus_manifest,
                                        full_store):
    """The satellite drill: kill a campaign after 2 cells (the cell-budget
    hook), restart it, and the final store is byte-for-byte the store of
    an uninterrupted run — and no cell ran twice."""
    store = CampaignStore(str(tmp_path / "store"))
    first = run_campaign(corpus_manifest, store, max_cells=2)
    assert first.counts == {"total": 6, "skipped": 0, "executed": 2,
                            "quarantined": 0, "remaining": 4}
    # "restart": a fresh handle on the same directory, no carried state
    resumed = run_campaign(corpus_manifest, CampaignStore(store.root))
    assert resumed.counts["skipped"] == 2
    assert resumed.counts["remaining"] == 0
    e1, e2 = set(first.executed), set(resumed.executed)
    assert e1.isdisjoint(e2) and e1 | e2 == set(full_store.completed())
    # the journal agrees nothing executed twice across both invocations
    done = [json.loads(l)["key"]
            for l in open(os.path.join(store.root, store.JOURNAL))
            if json.loads(l)["event"] == "done"]
    assert len(done) == len(set(done)) == 6
    # bit-identity of the cells/ tree vs the uninterrupted reference
    fa = sorted(os.listdir(os.path.join(full_store.root, "cells")))
    fb = sorted(os.listdir(os.path.join(store.root, "cells")))
    assert fa == fb
    for fn in fa:
        ref = open(os.path.join(full_store.root, "cells", fn), "rb").read()
        got = open(os.path.join(store.root, "cells", fn), "rb").read()
        assert ref == got, f"cell file {fn} differs after crash-restart"


def test_quarantine_keeps_campaign_alive_and_sticks(tmp_path):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    ingest.write_csv(str(corpus / "good.csv"), [1, 2, 1, 3], [8, 8, 8, 8])
    # 10 bytes is not a whole number of 24-byte oracle records
    (corpus / "bad.oracleGeneral.bin").write_bytes(b"\x00" * 10)
    m = scan_corpus(str(corpus), name="q",
                    grid=Grid(policies=("lru",), K=(4,), seeds=(0,)),
                    dataset="d", characterize=False)
    store = CampaignStore(str(tmp_path / "store"))
    summary = run_campaign(m, store)
    assert len(summary.executed) == 1 and len(summary.quarantined) == 1
    q = store.get_quarantined(summary.quarantined[0])
    assert "Traceback" in q["error"]
    assert q["cell"]["trace"].endswith("bad.oracleGeneral.bin")
    # resume: the quarantined cell is not retried, nothing is pending
    assert pending_cells(plan_cells(m), store) == []
    again = run_campaign(m, store)
    assert again.counts["executed"] == 0 and again.counts["skipped"] == 2


def test_workers_spawn_pool(tmp_path, corpus_manifest):
    """A 2-worker process pool completes the same cells as inline runs
    (spawn context; results land via the shared store directory)."""
    grid = Grid(policies=("fifo", "lru"), K=(32,), seeds=(0,), T=800)
    m = dataclasses.replace(
        corpus_manifest, grid=grid,
        datasets=tuple(d for d in corpus_manifest.datasets
                       if d.name == "txt"))
    store = CampaignStore(str(tmp_path / "store"))
    summary = run_campaign(m, store, workers=2)
    assert summary.counts["executed"] == 2
    assert summary.counts["quarantined"] == 0
    assert len(store.completed()) == 2


@pytest.mark.parametrize("backend", ["tpu", "gpu"])
def test_workers_refuse_pool_off_cpu(tmp_path, corpus_manifest, monkeypatch,
                                     backend):
    """On an accelerator the parent holds the device, so a worker pool
    would fight it for the chip: refuse before any cell runs."""
    import jax
    grid = Grid(policies=("fifo", "lru"), K=(32,), seeds=(0,), T=800)
    m = dataclasses.replace(
        corpus_manifest, grid=grid,
        datasets=tuple(d for d in corpus_manifest.datasets
                       if d.name == "txt"))
    store = CampaignStore(str(tmp_path / "store"))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match="inline"):
        run_campaign(m, store, workers=2)
    assert store.completed() == []


# --- winners tie-break / margin + CDF (bench.report satellites) ------------

def _rec(policy, scenario, miss):
    return {"policy": policy, "scenario": scenario, "K_label": "S",
            "seeds": [0], "dataset": "d",
            "metrics": {"miss_ratio": [miss], "hit_ratio": [1 - miss],
                        "byte_miss_ratio": [miss], "penalty_ratio": [miss]}}


def test_winners_tie_breaks_lexicographically_with_margin():
    recs = [_rec("zpol", "t", 0.4), _rec("apol", "t", 0.4),
            _rec("mpol", "t", 0.6)]
    pols = ["zpol", "apol", "mpol"]
    plain = bench_report.winners(recs, pols)
    assert plain["t(S)"] == {"apol": 1.0}          # tie -> first by name
    assert sum(plain["t(S)"].values()) == 1.0      # shape unchanged
    rich = bench_report.winners(recs, pols, margin=True)
    assert rich["t(S)"]["winners"] == {"apol": 1.0}
    assert rich["t(S)"]["margin"] == pytest.approx(0.0)  # runner-up tied
    solo = bench_report.winners([_rec("a", "t", 0.3), _rec("b", "t", 0.5)],
                                ["a", "b"], margin=True)
    assert solo["t(S)"]["margin"] == pytest.approx(0.2)


def test_metric_cdf_is_a_cdf():
    recs = [_rec("a", f"t{i}", m) for i, m in enumerate([0.2, 0.6, 0.4])]
    cdf = bench_report.metric_cdf(recs, ["a"], "miss_ratio")["a"]
    assert cdf["values"] == sorted(cdf["values"])
    assert cdf["cdf"][-1] == pytest.approx(1.0)
    assert all(x <= y for x, y in zip(cdf["cdf"], cdf["cdf"][1:]))


# --- report layer, from the store alone ------------------------------------

def test_report_renders_from_store_alone(full_store):
    report = render_report(full_store, baseline="fifo")
    assert report["n_cells"] == 6 and report["n_quarantined"] == 0
    assert report["policies"] == ["fifo", "lru"]
    assert set(report["winners"]) == {"csv", "oracle", "txt"}
    for row in report["winners"].values():
        assert row["winner"] in ("fifo", "lru")
        assert row["margin"] >= 0.0
        assert sum(row["wins"].values()) == pytest.approx(1.0)
    # reduction tables: fifo vs itself is exactly zero
    for col in report["mrr_vs_fifo"].values():
        assert col["fifo"] == pytest.approx(0.0)
    cdf = report["hit_ratio_cdf"]
    assert set(cdf) == {"fifo", "lru"} and len(cdf["lru"]["values"]) == 3
    text = format_report(report)
    assert "winners (miss ratio)" in text and "oracle" in text


def test_incomplete_cells_shrink_tables_not_crash(full_store):
    recs = campaign_records(full_store)
    # drop one policy's record from one cell -> that cell leaves the table
    recs = [r for r in recs
            if not (r["policy"] == "lru" and r["dataset"] == "txt")]
    table = dataset_winners(recs, ["fifo", "lru"])
    assert "txt" not in table and set(table) == {"csv", "oracle"}
    assert all(row["dropped"] == 0 for row in table.values())


# --- results --out-dir plumbing + ingest cache key (satellites) ------------

def test_set_results_dir_redirects_save(tmp_path, monkeypatch):
    monkeypatch.setattr(results, "RESULTS_DIR", results.RESULTS_DIR)
    out = str(tmp_path / "elsewhere")
    assert results.set_results_dir(out) == out
    path = results.save(_tiny_payload())
    assert os.path.dirname(path) == out
    assert not [f for f in os.listdir(out) if ".tmp." in f]   # atomic


def test_characterize_cache_keys_on_size(tmp_path):
    """A rewrite that lands in the same mtime tick must not serve stale
    stats to make_manifest: file size is part of the cache key."""
    p = str(tmp_path / "t.csv")
    ingest.write_csv(p, [1, 2], [8, 8])
    mtime_ns = os.stat(p).st_mtime_ns
    assert ingest.characterize(p).n_requests == 2
    ingest.write_csv(p, [1, 2, 3, 4], [8, 8, 8, 8])
    os.utime(p, ns=(mtime_ns, mtime_ns))           # force same-mtime rewrite
    assert ingest.characterize(p).n_requests == 4
    assert ingest.count_requests(p) == 4


# --- CLI --------------------------------------------------------------------

def _cli(args, **kw):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-m", "benchmarks.campaign",
                           *args], capture_output=True, text=True,
                          cwd=str(ROOT), env=env, **kw)


def test_cli_status_and_report_from_store_only(full_store):
    out = _cli(["--store", full_store.root, "--status"])
    assert out.returncode == 0, out.stderr
    assert "completed   6" in out.stdout
    out = _cli(["--store", full_store.root, "--report"])
    assert out.returncode == 0, out.stderr
    assert "winners (miss ratio)" in out.stdout
    report = json.load(open(os.path.join(full_store.root, "report.json")))
    assert report["schema"] == "repro.campaign.report/v1"
    assert report["n_cells"] == 6


def test_cli_fresh_store_requires_manifest(tmp_path):
    out = _cli(["--store", str(tmp_path / "fresh")])
    assert out.returncode == 2
    assert "--manifest is required" in out.stderr


def test_run_out_dir_flag_redirects_results(tmp_path):
    """`benchmarks.run --out-dir` repoints the live results directory."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; from benchmarks import run\n"
         "run.main(['--out-dir', sys.argv[1], '--list'])\n"
         "from repro.bench import results\n"
         "print(results.RESULTS_DIR)",
         str(tmp_path / "out")],
        capture_output=True, text=True, cwd=str(ROOT),
        env=dict(os.environ, PYTHONPATH="src"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path / "out")
