"""The int8 codec's kernels (repro.kernels.quantize) against the HBM
roofline: the bytes their shapes require for every client lane of the
rounds in the traced window (the model's ``codec_bytes``; har-mlp:
``bench.flops.codec_bytes``) at the chips' peak bandwidth, over the device
time of their events (mean over chips).

A codec kernel is a custom call that the codec's ``quantize`` or
``dequantize`` wrapper emits, found by that name in the op's text (the
instruction's name, ``jit_quantize``, or its ``op_name`` metadata,
``jit(quantize)/pallas_call``). A round runs one quantize and one
dequantize per parameter leaf (the model's ``codec_leaves``) for all its
lanes at once on each chip; any other count of matched events is an
error, so that a kernel that is not the codec's cannot move the metric."""

import re

from bench import trace_reduce

CODEC = re.compile(r"jit_(?:de)?quantize|jit\((?:de)?quantize\)")


def read(facts):
    if facts.peak is None or facts.recipe["codec"] != "int8" or facts.rounds == 0:
        return None
    red = facts.reduced
    names = [k for k, text in red["op_text"].items()
             if trace_reduce.opcode(text) in ("custom-call", "") and CODEC.search(text)]
    per_round = 2 * facts.model.codec_leaves(facts)  # a quantize and a dequantize a leaf
    events = sum(red["op_count"][k] for k in names)
    if events != per_round * facts.rounds * facts.chips:
        raise ValueError(f"quantize_roofline: {events} codec kernel events in the traced "
                         f"window, expected {per_round} a round x {facts.rounds} rounds "
                         f"x {facts.chips} chips")
    kernel_ns = sum(red["op_ns"][k] for k in names)
    moved = facts.model.codec_bytes(facts)
    return 100.0 * (moved / (facts.peak["hbm_bw"] * facts.chips)) / (kernel_ns * 1e-9)
