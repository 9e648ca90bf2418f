"""The contract checker: a good line passes, each malformed one is refused
with a reason, and the committed BENCHMARK.json keeps the contract's static
rules (so a later PR that adds a cell finds out here, not on the chip)."""

import copy
import io
import json
import math

import pytest

from perfbench.lib import contract
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held

MANIFEST = manifest_lib.load()


def _serve_cell():
    """A serving cell (the kind whose traced last line PR 22's driver
    could not read)."""
    for w in MANIFEST["workloads"]:
        names = {m["name"] for m in
                 manifest_lib.metrics_for(MANIFEST, w["name"], False)}
        if "gap_p95_ms" in names:
            return w["name"]
    return MANIFEST["workloads"][0]["name"]


CELL = _serve_cell()


def good_line(traced: bool):
    metrics = {m["name"]: {"value": 12.5, "unit": m["unit"]}
               for m in manifest_lib.metrics_for(MANIFEST, CELL, traced)}
    chips = manifest_lib.workload(MANIFEST, CELL)["chips"]
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips,
              "memory_peak_bytes": 13958643712}
    breakdown = None
    if traced:
        device.update(window_s=3.0, busy_s=1.25)
        breakdown = {"device_ops": [["fusion", 0.5]],
                     "idle_gaps": [["a -> b | host: x", 0.25]]}
    return contract.build_line(correct=True, attempted=400, failed=0,
                               metrics=metrics, device=device,
                               breakdown=breakdown)


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_is_accepted_and_printed(traced):
    line = good_line(traced)
    assert contract.check_line(line, MANIFEST, CELL, traced) == []
    out = io.StringIO()
    contract.emit(line, MANIFEST, CELL, traced, out=out)
    assert json.loads(out.getvalue()) == line
    assert contract.check_line(out.getvalue(), MANIFEST, CELL, traced) == []


def _drop(key):
    return lambda line: line.pop(key)


def _set_metric(value):
    def change(line):
        first = next(iter(line["metrics"]))
        line["metrics"][first]["value"] = value
    return change


def _device(**fields):
    def change(line):
        for k, v in fields.items():
            if v is KeyError:
                line["device"].pop(k)
            else:
                line["device"][k] = v
    return change


MALFORMED = {
    "no_correct": (True, _drop("correct")),
    "no_attempted": (True, _drop("attempted")),
    "no_failed": (True, _drop("failed")),
    "no_metrics": (True, _drop("metrics")),
    "no_device": (True, _drop("device")),
    "metric_null": (True, _set_metric(None)),
    "metric_nan": (True, _set_metric(math.nan)),
    "metric_inf": (True, _set_metric(math.inf)),
    "metric_text": (True, _set_metric("12.5")),
    "metric_bool": (True, _set_metric(True)),
    "metric_missing_for_the_cell":
        (True, lambda l: l["metrics"].pop(next(iter(l["metrics"])))),
    "metric_not_listed":
        (True, lambda l: l["metrics"].update(
            made_up={"value": 1.0, "unit": "s"})),
    "metric_wrong_unit":
        (True, lambda l: l["metrics"][next(iter(l["metrics"]))].update(
            unit="furlongs")),
    "metric_bare_number":
        (True, lambda l: l["metrics"].update(
            {next(iter(l["metrics"])): 12.5})),
    "end_to_end_zero": (False, _set_metric(0)),
    "traced_without_window_s": (True, _device(window_s=KeyError)),
    "traced_without_busy_s": (True, _device(busy_s=KeyError)),
    "busy_s_zero": (True, _device(busy_s=0.0)),
    "busy_s_over_window_s": (True, _device(busy_s=3.5)),
    "busy_s_null": (True, _device(busy_s=None)),
    "no_memory_peak": (True, _device(memory_peak_bytes=KeyError)),
    "memory_peak_zero": (False, _device(memory_peak_bytes=0)),
    "wrong_device_count": (False, _device(count=3)),
    "no_platform": (False, _device(platform=KeyError)),
    "more_failed_than_attempted": (False, lambda l: l.update(failed=401)),
    "nothing_attempted": (False, lambda l: l.update(attempted=0)),
    "correct_as_text": (False, lambda l: l.update(correct="true")),
    "breakdown_in_untraced_run":
        (False, lambda l: l.update(breakdown={"device_ops": [],
                                              "idle_gaps": []})),
    "breakdown_too_long":
        (True, lambda l: l["breakdown"].update(
            device_ops=[["op", 0.1]] * 11)),
    "breakdown_row_shape":
        (True, lambda l: l["breakdown"].update(idle_gaps=[["gap"]])),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_line_is_refused(case):
    traced, change = MALFORMED[case]
    line = copy.deepcopy(good_line(traced))
    change(line)
    problems = contract.check_line(line, MANIFEST, CELL, traced)
    assert problems, case
    with pytest.raises(contract.ContractError):
        contract.emit(line, MANIFEST, CELL, traced, out=io.StringIO())


@pytest.mark.parametrize("text", ["", "not json", "[1, 2]",
                                  '{"correct": NaN}'])
def test_text_that_is_no_object_is_refused(text):
    assert contract.check_line(text, MANIFEST, CELL, False)


def test_cli_reads_the_last_line(tmp_path, monkeypatch, capsys):
    good = json.dumps(good_line(False))
    monkeypatch.setattr("sys.stdin", io.StringIO("a note\n" + good + "\n\n"))
    assert contract.main(["--workload", CELL, "--trace", "0"]) == 0
    monkeypatch.setattr("sys.stdin", io.StringIO(good + "\nlast words\n"))
    assert contract.main(["--workload", CELL, "--trace", "0"]) == 1
    assert "REFUSED" in capsys.readouterr().out


# ------------------------------------------------ the committed manifest
# The rules themselves are functions of a manifest (``held.py``), so that
# ``test_perfbench_additions.py`` can hold a manifest with a later PR's
# additions to them too.


def test_manifest_keys_names_and_units():
    held.static_rules(MANIFEST)


def test_every_cell_reports_what_the_contract_asks():
    held.every_cell_reports(MANIFEST)


def test_every_named_file_is_there():
    held.every_named_file_is_there(MANIFEST)
