"""How a serving cell puts NeuroAda deltas into the engine, one module per
mode, named by the traffic mix's ``adapter`` key.

Each module has ``build(cell, model, params) -> Served``: the engine, the
keyword arguments that submit a request of a tenant, and the selection and
values of that tenant's delta, which the plain reference reads.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Callable


@dataclass
class Served:
    engine: object
    submit_kwargs: Callable[[int], dict]  # tenant -> ServeEngine.submit keywords
    delta: Callable[[int], tuple]  # tenant -> (idx, vals), one leaf per adapted linear


def build(cell, model, params) -> Served:
    return importlib.import_module(f"bench.adapters.{cell.mix['adapter']}").build(cell, model, params)


def engine_kwargs(cell) -> dict:
    """The engine settings of the configuration file."""
    eng = cell.cfg["engine"]
    return dict(slots=eng["slots"], max_len=eng["max_len"], eos_id=eng["eos_id"],
                temperature=0.0, decode_chunk=eng["decode_chunk"],
                prefill_chunk=eng["prefill_chunk"], paged=True, page_size=eng["page_size"],
                num_blocks=eng["num_blocks"], kv_dtype=eng["kv_dtype"],
                base_dtype=cell.hooks.get("base_dtype", "fp32"))


def make_engine(cell):
    """``ServeEngine``, or the factory a fault or control puts in its place."""
    from repro.serve import ServeEngine

    return cell.hooks.get("engine", ServeEngine)
