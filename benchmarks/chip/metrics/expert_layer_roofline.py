"""The expert layer's share of its roofline: the least time the chip could
take for every MoE layer of the window's decode and prefill calls (each
reads the router, the shared expert and every held expert once, and
spends two operations per weight a token passes through, the held experts
counted at their expectation: ``layouts/mla_moe.expert_layer_work``) over
the device time of the program ``jit_expert_layer`` in the trace."""

import pathlib

from chipbench import harness

MODULE = "jit_expert_layer"


def read(rec):
    t = rec.trace.module_s.get(MODULE) if rec.trace else None
    launches = rec.decode_launches + rec.prefill_launches
    if not t or not launches or "published_n_routed_experts" not in rec.cfg:
        return None
    layout = harness.load_module(pathlib.Path(__file__).resolve().parents[1],
                                 "layouts", rec.cfg["reference"])
    layers = (rec.cfg["num_hidden_layers"]
              - rec.cfg["first_k_dense_replace"])
    need = layers * sum(
        layout.expert_layer_work(rec.cfg, w.tokens).seconds(rec.peaks)
        for w in launches)
    return 100.0 * need / t
