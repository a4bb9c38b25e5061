"""The per-layer readers of a cell on several chips, on a hand-made trace of
four devices: collective time inside the prefill and decode-step spans
(``allreduce_ms.*``), and the whole-step shares over the peak of every chip
in the trace (``*_per_chip``)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import collectives, run, trace  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
COUNTS = {"prefills": 1, "prefill_flops": 3e14, "decode_steps": 2,
          "decode_flops": 4e12, "decode_bytes": 2e10}

# every form of a collective the v5e trace may name, and names that are not
COLLECTIVE = ["%all-reduce.9", "%all-reduce-start.2", "%all-reduce-done.2",
              "%all-gather.1", "%all-gather-start", "%all-gather-done.1",
              "%reduce-scatter.3", "%collective-permute-start.1",
              "%collective-permute-done.1", "%all-to-all.4", "%all-reduce-scatter-fusion.1",
              "%fusion_all-reduce.2"]
OTHER = ["%fusion.12", "%reduce.3", "%scatter.1", "%gather.5", "%while.3",
         "%reduce-window.1", "%copy_dynamic-update-slice_fusion.4", "%select-and-scatter"]


def reader(name):
    return run.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py").read


def four_chips():
    """Prefill in [0, 4), two decode steps in [4, 5) and [5, 6). Device d runs
    compute over each whole span, and inside it collectives: in prefill one of
    each form, 0.1 s apart and 0.01 * (d + 1) s long; in each decode step one
    all-reduce of 0.02 s. Non-collective ops fill the gaps."""
    ops = []
    for d in range(4):
        dev = [("jit_prefill_step/%while.1", 0.0, 4.0),
               ("jit_decode_step/%while.1", 4.0, 5.0),
               ("jit_decode_step/%while.1", 5.0, 6.0)]
        for i, name in enumerate(COLLECTIVE):
            t = 0.1 * (i + 1)
            dev.append(("jit_prefill_step/" + name, t, t + 0.01 * (d + 1)))
        for i, name in enumerate(OTHER):
            t = 2.0 + 0.1 * i
            dev.append(("jit_prefill_step/" + name, t, t + 0.05))
        for s in (4.0, 5.0):
            dev.append(("jit_decode_step/%all-reduce.7", s + 0.5, s + 0.52))
            dev.append(("jit_decode_step/%fusion.3", s + 0.1, s + 0.4))
        ops.append(dev)
    spans = [("cb:unit", 0.0, 6.5), ("cb:prefill", 0.0, 4.0),
             ("cb:decode_step", 4.0, 5.0), ("cb:decode_step", 5.0, 6.0)]
    return trace.Summary(ops=ops, spans=spans)


@pytest.mark.parametrize("name", COLLECTIVE + OTHER)
def test_collective_names(name):
    assert collectives.is_collective("jit_decode_step/" + name) == (name in COLLECTIVE)


def test_allreduce_ms_counts_every_form_and_nothing_else():
    r = run.Reading(four_chips(), COUNTS, PEAK, 0.0)
    # prefill: 12 collectives of 0.01 * (d + 1) s, averaged over d = 0..3
    assert reader("allreduce_ms.prefill")(r) == pytest.approx(
        1e3 * len(COLLECTIVE) * 0.01 * 2.5)
    # decode: one 0.02 s all-reduce a step on every chip
    assert reader("allreduce_ms.decode")(r) == pytest.approx(20.0)


@pytest.mark.parametrize("name,one_chip", [
    ("mfu.serve_per_chip", "mfu.serve"),
    ("mfu.prefill_per_chip", "mfu.prefill"),
    ("hbm_roofline.decode_per_chip", "hbm_roofline.decode"),
])
def test_per_chip_shares_are_a_quarter_on_four_chips(name, one_chip):
    r = run.Reading(four_chips(), COUNTS, PEAK, 0.0)
    whole = reader(one_chip)(r)
    assert whole > 0
    assert reader(name)(r) == pytest.approx(whole / 4)


@pytest.mark.parametrize("name", ["allreduce_ms.prefill", "allreduce_ms.decode",
                                  "mfu.serve_per_chip", "mfu.prefill_per_chip",
                                  "hbm_roofline.decode_per_chip"])
def test_readers_are_silent_without_their_spans(name):
    """A trace with units but no prefill or decode-step spans, and no counts
    of them (a training cell's), gives no reading."""
    s = four_chips()
    bare = trace.Summary(ops=s.ops, spans=[sp for sp in s.spans if sp[0] == "cb:unit"])
    assert reader(name)(run.Reading(bare, {}, PEAK, 0.0)) is None
    if name.startswith("allreduce"):  # spans but no collective in them
        quiet = trace.Summary(ops=[[op for op in dev if not collectives.is_collective(op[0])]
                                   for dev in s.ops], spans=s.spans)
        assert reader(name)(run.Reading(quiet, COUNTS, PEAK, 0.0)) is None
