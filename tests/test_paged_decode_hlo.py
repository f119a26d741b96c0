"""The serving decode program writes the layer scan's KV pools in place.

The scheduler's donated decode jit is compiled on the XLA backend for
smoke configurations, and its optimised HLO is read: the stacked pools
of the scanned layers are loop-carried state, updated by one scatter
per layer and read by a gather of the table's pages, so no instruction
copies, fills, slices out or writes back a whole pool, be it the stack
or one layer's, and both pools alias their donated inputs.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import build_model, get_smoke_config
from repro.serve.scheduler import ContinuousBatchingScheduler

pytestmark = pytest.mark.timeout(300)

#: opcodes that would copy, fill, slice out or write back a whole pool
MOVERS = ("copy", "broadcast", "dynamic-slice", "dynamic-update-slice")

_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
_ALIAS = re.compile(r"\{[\d,]*\}: \((\d+),")
_POOL_PARAM = re.compile(r"parameter\((\d+)\), metadata=\{op_name=\"pool\[")


def _decode_hlo(arch, monkeypatch):
    """The scheduler's decode program, compiled; its stacked pools."""
    monkeypatch.setenv("ADSALA_BACKEND", "xla")
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=3, n_pages=48, page_size=4,
        max_seq_len=24)
    b = sched.slots
    args = (params, sched.pool, jnp.zeros((b, 1), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, sched.table_pages), jnp.int32))
    hlo = sched._decode.lower(*args).compile().as_text()
    return hlo, jax.tree.leaves(sched.pool["scan"])


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "granite-8b"])
def test_decode_moves_no_whole_pool(arch, monkeypatch):
    hlo, stacked = _decode_hlo(arch, monkeypatch)
    assert len(stacked) == 2                   # one layer-scan K and V
    shapes = set()
    for a in stacked:
        shapes |= {a.shape, a.shape[1:], (1,) + a.shape[1:]}
    movers, writes = [], 0
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        if dims in shapes and m.group(2) in MOVERS:
            movers.append(line.strip()[:160])
        writes += dims == stacked[0].shape and m.group(2) == "scatter"
    assert not movers, "\n".join(movers)
    # the mechanism engages: each pool is written by a scatter into it
    assert writes >= len(stacked)
    header = hlo.splitlines()[0]
    aliased = {int(n) for n in _ALIAS.findall(header)}
    pool_params = {int(n) for n in _POOL_PARAM.findall(hlo)}
    assert len(pool_params) == len(stacked)
    assert pool_params <= aliased, header[:300]
