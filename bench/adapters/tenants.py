"""Many tenants' deltas kept unmerged in an ``AdapterStore`` beside one
base, each request naming its tenant (``tenants.count`` in the traffic
mix). Every tenant shares the magnitude selection of the base (k = 1) and
has seeded values of its own."""

from __future__ import annotations

from bench import weights
from bench.adapters import Served, engine_kwargs, make_engine


def build(cell, model, params) -> Served:
    from repro.serve.adapters import AdapterStore

    idx = weights.select_top1(params["blocks"])
    count = cell.mix["tenants"]["count"]
    vals = {t: weights.tenant_values(cell.cfg, cell.mix["tenant_value_std"], cell.seed, t)
            for t in range(1, count + 1)}
    store = AdapterStore(params)
    for t in range(1, count + 1):
        if store.register(weights.program_tree(params, idx),
                          weights.program_tree(params, vals[t])) != t:
            raise SystemExit("bench: the adapter store numbered a tenant out of turn")
    engine = make_engine(cell)(model, params, adapter_store=store, **engine_kwargs(cell))
    return Served(engine, lambda tenant: {"adapter_id": tenant},
                  lambda tenant: (idx, vals[tenant]))
