"""The system under test: the int8 VWW network on the CFU fast path.

``build`` compiles the configuration's network with the program's own
compiler, packs the benchmark's weights into the program's parameter
records the way ``models.mobilenetv2.init_and_quantize`` and
``cfu.network.vww_cfu_params`` hand them to users (DSC blocks as device
arrays, stem, head and classifier as host arrays), and returns the public
entry a user calls: ``repro.cfu.fastpath.fast_executor(prog, params)``,
called as ``ex(x_q, params)``.
"""

from __future__ import annotations

import numpy as np


def build(cfg, weights, ref):
    """The entry ``x_q -> int8 logits`` and the executor behind it."""
    from repro.cfu.compiler import compile_vww_network
    from repro.cfu.fastpath import fast_executor
    from repro.cfu.network import vww_cfu_params
    from repro.core.dsc import DSCBlockSpec, QuantizedDSCParams
    from repro.core.quant import QParams
    from repro.models.mobilenetv2 import MobileNetV2Params

    dom = {k: QParams(scale=s, zero_point=z)
           for k, (s, z) in ref.domains(cfg).items()}
    q6 = ref.relu6_cap(ref.domains(cfg)["relu6"])
    specs, blocks = [], []
    qp_in = dom["relu6"]
    for (name, cin, cmid, cout, stride), bw in zip(cfg["blocks"],
                                                    weights["blocks"]):
        spec = DSCBlockSpec(cin=cin, cmid=cmid, cout=cout, stride=stride)
        specs.append((name, spec))
        blocks.append(QuantizedDSCParams(
            spec=spec, w_exp=bw["w_exp"], w_dw=bw["w_dw"],
            w_proj=bw["w_proj"], b_exp=bw["b_exp"], b_dw=bw["b_dw"],
            b_proj=bw["b_proj"], qp_in=qp_in, qp_f1=dom["relu6"],
            qp_f2=dom["relu6"], qp_out=dom["linear"], m_exp=bw["m_exp"],
            m_dw=bw["m_dw"], m_proj=bw["m_proj"], q6_f1=q6, q6_f2=q6))
        qp_in = dom["linear"]
    st, hd, fc = weights["stem"], weights["head"], weights["fc"]
    net = MobileNetV2Params(
        stem_w=st["w"], stem_b=st["b"], stem_m=st["m"],
        qp_img=dom["image"], qp_stem=dom["relu6"], blocks=blocks,
        head_w=hd["w"], head_b=hd["b"], head_m=hd["m"], qp_head=dom["relu6"],
        fc_w=fc["w"], fc_b=fc["b"], fc_m=fc["m"], qp_logits=dom["logits"])
    params = vww_cfu_params(net)
    prog = compile_vww_network(specs, cfg["img_hw"], cfg["schedule"],
                               img_ch=cfg["img_ch"], head_ch=cfg["head_ch"],
                               n_classes=cfg["n_classes"])
    ex = fast_executor(prog, params)

    def entry(x_q: np.ndarray) -> np.ndarray:
        return ex(x_q, params)
    return entry, ex


def release():
    """Drop the program's cached executors (and their device state)."""
    from repro.cfu.fastpath import clear_cache
    clear_cache()
