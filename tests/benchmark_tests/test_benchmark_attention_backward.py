"""`attention_backward_roofline_share` (PR 25): the backward pass's least
work by hand, the reader on made-up rows, on the recording of a program whose
backward was plain XLA (PR 24: nothing to read, nothing raised) and on the
kernels' rows of a traced run of `mistral-7b-v0.3.train-s4096` from the chip
(PR 25, TPU v5 lite; `data/kernels_mistral_s4096.json`: the `_flash_*` events
and the programs of device 0's last two steps, as
`scripts/chip_trace_fixture.py` recorded them)."""

import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.manifest import Manifest

DATA = Path(__file__).resolve().parent / "data"
P0 = "/device:TPU:0"
MANIFEST = Manifest()
COST = MANIFEST.module("flops", "attention_backward")
READER = MANIFEST.module("layer_metrics", "attention_backward_roofline_share")
PEAKS = json.loads((Path(tr.__file__).parent / "peaks.json").read_text())["TPU v5 lite"]


def decoder_run(rows):
    return {
        "trace_rows": rows, "chips": 1, "peaks": PEAKS, "manifest": MANIFEST,
        "config": MANIFEST.config("mistral-7b-v0.3"),
        "traffic": MANIFEST.json("traffic", "train-s4096"),
    }


def recorded_rows(name):
    c = json.loads((DATA / name).read_text())
    return [[c["planes"][p], c["lines"][l], c["names"][n], s, d] for p, l, n, s, d in c["rows"]], c


def least_seconds():
    return COST.flops(2, 4096, 32, 128) / PEAKS["bf16_flops_per_s"]


def test_backward_flops_and_bytes_by_hand():
    # 2 sequences, 4096 tokens, 32 query heads on 8 key/value heads of 128:
    # five block matmuls of 2 * 128 FLOPs a score over the causal half.
    assert COST.flops(2, 4096, 32, 128) == 5 * 2 * 128 * 2 * 32 * 4096 * 4096 / 2
    forward = MANIFEST.module("flops", "attention")
    assert COST.flops(2, 4096, 32, 128) == 2.5 * forward.flops(2, 4096, 32, 128)
    q_out_dout_dq = 4 * 2 * 4096 * 32 * 128 * 2
    k_v_dk_dv = 4 * 2 * 4096 * 8 * 128 * 2
    lse_and_delta = 3 * 2 * 32 * 4096 * 4
    assert COST.bytes_moved(2, 4096, 32, 8, 128) == q_out_dout_dq + k_v_dk_dv + lse_and_delta
    memory = COST.bytes_moved(2, 4096, 32, 8, 128) / PEAKS["hbm_bytes_per_s"]
    assert least_seconds() > 5 * memory  # compute-bound at this length
    assert least_seconds() == pytest.approx(3.488e-3, rel=1e-3)


@pytest.mark.parametrize(
    "kernels, matmuls",
    [(("_flash_backward_dkv", "_flash_backward_dq"), 7), (("_flash_backward",), 5)],
)
def test_a_design_that_recomputes_reads_under_its_matmuls_share(kernels, matmuls):
    """Kernels that ran at the MXU's peak: a fused one reads 100%, the
    two-kernel design 5/7 of it, because the least work is the algorithm's."""
    each = int(1e9 * least_seconds() * matmuls / 5 / len(kernels))
    rows, t = [], 0
    for call in range(3):  # three layers' passes; the name's suffix is the call site's
        for k in kernels:
            rows.append([P0, tr.OP_LINE, f"%{k}.{7} = custom-call()", t, each])
            t += each + 1000
        rows.append([P0, tr.OP_LINE, "%_flash_forward.2 = custom-call()", t, 10**6])
        t += 10**6
    run = decoder_run(rows)
    assert READER.read(run) == pytest.approx(100.0 * 5 / matmuls, rel=1e-3)
    note = run["notes"]["attention_backward_roofline"]
    assert note["passes"] == 3 and note["bound"] == "compute"
    assert sum(note["kernel_calls"].values()) == 3 * len(kernels)


def test_nothing_to_read_without_the_kernels_or_outside_the_decoder():
    rows, _ = recorded_rows("spans_mistral_s4096.json")  # PR 24: the backward was an XLA scan
    assert any("_flash_forward" in r[2] for r in rows)
    run = decoder_run(rows)
    assert READER.read(run) is None and "notes" not in run
    assert READER.read({"traffic": {"input": "tokens"}}) is None  # no trace
    images = decoder_run([[P0, tr.OP_LINE, "_flash_backward_dq.1", 0, 1000]])
    images["traffic"] = {"input": "images"}
    assert READER.read(images) is None


def test_on_the_kernels_rows_of_a_traced_run_from_the_chip():
    rows, c = recorded_rows("kernels_mistral_s4096.json")
    assert c["device"] == "TPU v5 lite" and c["cell"] == "mistral-7b-v0.3.train-s4096"
    names = {tr.short_name(r[2]).rsplit(".", 1)[0] for r in rows if r[1] == tr.OP_LINE}
    assert names == {"_flash_forward", "_flash_backward_dkv", "_flash_backward_dq"}
    programs = sum(r[1] == tr.MODULE_LINE for r in rows)
    layers = MANIFEST.config("mistral-7b-v0.3")["num_hidden_layers"]
    run = decoder_run(rows)
    share = READER.read(run)
    note = run["notes"]["attention_backward_roofline"]
    assert note["passes"] == programs * layers
    assert note["kernel_calls"] == {
        "_flash_backward_dkv": programs * layers, "_flash_backward_dq": programs * layers
    }
    # what the run itself printed over its four traced programs, and under the
    # two-kernel design's 5/7
    assert share == pytest.approx(c["attention_backward_roofline_share"], rel=0.01)
    assert 30 < share < 100 * 5 / 7
    # the forward kernel's own share is read from the same rows, as before
    forward = MANIFEST.module("layer_metrics", "attention_roofline_share").read(decoder_run(rows))
    assert forward == pytest.approx(c["attention_roofline_share"], rel=0.01)
