"""Every part that ``BENCHMARK.json`` names is found by name."""
import os

from chipbench import plugins, run


def test_every_named_part_loads():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for cfg in bench["configs"]:
        config = run.load_json(os.path.join(run.ROOT, cfg["file"]))
        assert callable(plugins.load("entries", config["entry"]).drive)
        ref = plugins.load("reference", config["reference"])
        assert any(callable(getattr(ref, name, None))
                   for name in ("replay_chunks", "replay_lanes"))
    for cell in bench["workloads"]:
        mix = run.load_json(os.path.join(run.HERE, "traffic",
                                         cell["traffic"] + ".json"))
        source = plugins.load("generators", mix["generator"])
        assert callable(source.make) and callable(source.shrink)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(plugins.load("metrics", m["name"]).read)


def test_policy_spec_is_built_from_the_parameters():
    entry = plugins.load("entries", "replay_stream")
    config = {"policy": "dac", "policy_params": {"eps": 0.5, "growth": 4}}
    assert entry.policy_spec(config) == "dac(eps=0.5,growth=4)"
    from repro.core import make_policy
    assert make_policy(entry.policy_spec(config)).growth == 4


def test_row_roofline_counts_the_active_ranks_of_the_traced_chunks():
    read = plugins.load("metrics", "row_roofline_pct").read
    ctx = {"config": {"rank_bytes": 4}, "lanes": 10,
           "peak": {"hbm_bytes_per_s": 1e9},
           "traced": {"chunks": [1, 2], "busy_s": 1e-3},
           "ranks": {0: [999, 128, 256], 5: [999, 384, 512]}}
    # per lane over chunks 1-2: (384 + 896) / 2 = 640 ranks; 10 lanes x
    # 640 x 4 B x 2 = 51,200 B, 51.2 us at 1 GB/s, over 1 ms busy
    assert abs(read(ctx) - 5.12) < 1e-9
    assert read(dict(ctx, ranks={})) is None
    assert read(dict(ctx, config={})) is None
