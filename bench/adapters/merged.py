"""One tenant's delta merged into the base (NeuroAda Alg. 1 phase 3), served
with no bypass at inference."""

from __future__ import annotations

from bench import weights
from bench.adapters import Served, engine_kwargs, make_engine


def build(cell, model, params) -> Served:
    from repro.configs import PeftConfig
    from repro.peft import get_peft

    idx = weights.select_top1(params["blocks"])
    vals = weights.tenant_values(cell.cfg, cell.mix["tenant_value_std"], cell.seed)
    peft = get_peft(PeftConfig(method="neuroada", k=1))
    merged = peft.merge(params, weights.program_tree(params, vals),
                        weights.program_tree(params, idx))
    engine = make_engine(cell)(model, merged, **engine_kwargs(cell))
    return Served(engine, lambda tenant: {}, lambda tenant: (idx, vals))
