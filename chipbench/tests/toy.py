"""The benchmark's cells cut to a size a CPU test run holds."""

FEDNL_TOY = dict(silos=8, rows_per_silo=60, features=40, rounds_to_settle=20)
FEDNL_TOY_LEVEL = {"topk": 200, "blocktopk": 256}
TRAIN_TOY = dict(hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=512,
                 vocab_size=512, torch_dtype="float32")


def toy_cell(workload: str, setattr_=setattr) -> dict:
    """The cell as ``run.find_cell`` gives it, cut to a toy size: the
    program's reduced qwen2 for a training cell, two rows a silo.
    ``setattr_`` patches the program's configuration lookup (pytest's
    ``monkeypatch.setattr`` undoes it after the test)."""
    from chipbench import run

    cell = run.find_cell(workload)
    if cell["traffic_data"]["driver"] == "fednl_rounds":
        cell["config_data"].update(FEDNL_TOY)
        comp = cell["traffic_data"]["compressor"]
        cell["traffic_data"].update(rounds_per_call=20,
                                    level=FEDNL_TOY_LEVEL[comp])
    else:
        import repro.configs as rc

        full = rc.get_config
        setattr_(rc, "get_config", lambda name, smoke=False: full(name, True))
        cell["config_data"].update(TRAIN_TOY)
        cell["traffic_data"].update(batch=2 * int(cell["chips"]), seq=64,
                                    curvature_k=256)
    return cell


def benchmark_cells(driver: str, chips: int = 1) -> list:
    """Names of the cells of ``BENCHMARK.json`` whose traffic mix names
    ``driver`` and that take ``chips`` chips."""
    from chipbench import run

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"] if w["chips"] == chips
            and run.load_json(run.BENCH / "traffic" / f"{w['traffic']}.json"
                              )["driver"] == driver]
