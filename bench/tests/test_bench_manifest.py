"""BENCHMARK.json: names and units, and every file a cell names."""

import inspect

from bench import manifest
from bench.drivers import ycsb
from bench.metrics import reader

MAN = manifest.load()


def test_names_and_units():
    names = ([c["name"] for c in MAN["configs"]]
             + [w["name"] for w in MAN["workloads"]]
             + [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
             + [w["traffic"] for w in MAN["workloads"]]
             + [k for c in MAN["configs"] for k in c["reduced"]])
    for n in names:
        assert manifest.NAME.match(n), n
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert manifest.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in MAN[group]]
        assert len(got) == len(set(got)), group
    for text in ([c["why"] for c in MAN["configs"] + MAN["workloads"]]
                 + [m["layer"] for m in MAN["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_every_cell_resolves():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in e2e
    for w in MAN["workloads"]:
        cell = manifest.cell(w["name"], MAN)
        assert cell.chips in (1, 4)
        cfg = cell.config
        # what every cell's files carry, whatever its driver
        assert "guarantees" in cfg and cfg["chips"] == cell.chips
        assert "warmup_batches" in cell.traffic
        if cell.driver is ycsb:
            for key in ("n_nodes", "recordcount", "payload_width",
                        "clients_per_node", "write_back"):
                assert key in cfg, key
            assert {"read_proportion", "update_proportion",
                    "request_distribution",
                    "readback_records"} <= set(cell.traffic)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {x["name"] for x in cell.end_to_end}


def test_every_metric_has_a_reader():
    for m in MAN["per_layer"]:
        fn = reader(m["name"])
        assert list(inspect.signature(fn).parameters) == ["ctx"]


def test_reduced_keys_are_in_the_config_files():
    for c in MAN["configs"]:
        cfg = manifest.cell(next(w["name"] for w in MAN["workloads"]
                                 if w["config"] == c["name"]), MAN).config
        assert set(c["reduced"]) <= set(cfg), c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
