"""Paper Figs. 18/19/20: translation latency & metadata traffic by scheme.

Same populated mapping, five translation backends:
  utopia (RSW ∥ flat-flex), flat block table, radix 4-level walk,
  ECH (4 parallel probes), POM-TLB (probe + radix fill path).

Reports per-translation structure accesses, metadata bytes and wall-clock
µs per batch of device translations (+ the Pallas RSW kernel path).
The paper's headline: Utopia issues ~88% fewer memory requests than radix
and RSWs are ~7.6x faster than PTWs."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (HybridConfig, HybridKVManager, translate,
                        translate_radix, translate_ech, translate_pom,
                        RadixBuilder, ElasticCuckooTable, POMTLB)
from repro.kernels.utopia_rsw.ops import utopia_rsw
from common import csv_row, time_us, zipf_block_stream


def _setup(n_seqs=8, blocks=28, seed=0):
    cfg = HybridConfig(total_slots=512, restseg_fraction=0.75, assoc=8,
                       max_seqs=n_seqs, max_blocks_per_seq=32)
    m = HybridKVManager(cfg)
    radix = RadixBuilder(num_levels=4, fanout=8)
    ech = ElasticCuckooTable(capacity=256)
    pom = POMTLB(entries=128, ways=8)   # deliberately small: misses happen
    for s in range(n_seqs):
        m.register_sequence(s)
        for b in range(blocks):
            info = m.allocate_block(s, b)
            vpn = cfg.vpn(m.seq_slot(s), b)
            radix.map(vpn, info.slot)
            ech.insert(vpn, info.slot)
    stream = zipf_block_stream(n_seqs, blocks, 4096, seed=seed)
    vpns = jnp.asarray(stream[:, 0] * 32 + stream[:, 1], jnp.int32)
    return m, radix, ech, pom, vpns


def run() -> list:
    m, radix, ech, pom, vpns = _setup()
    ts = m.device_state()
    rtab = radix.device_table()
    est = ech.device_state()
    # fill POM with ~half the stream, then measure mixed hits/misses
    for v in np.asarray(vpns[:2048]):
        slot = m.blocks[int(v)].slot if int(v) in m.blocks else -1
        pom.lookup_fill(int(v), slot)
    pst = pom.device_state()
    ff = ts.flex.table.reshape(-1)

    backends = {
        "utopia": jax.jit(lambda v: translate(ts, v)),
        "flat": jax.jit(lambda v: ts.flex.lookup_vpn(v, 32)),
        "radix": jax.jit(lambda v: translate_radix(None, rtab, v)),
        "ech": jax.jit(lambda v: translate_ech(est, v)),
        "pom_tlb": jax.jit(lambda v: translate_pom(pst, rtab, v)),
    }
    # the Pallas kernel lowers through Mosaic on a TPU; elsewhere it runs
    # in the Pallas interpreter, and the row says so
    interpret = jax.default_backend() != "tpu"
    backends["utopia_rsw_kernel" + ("_interpret" if interpret else "")] = (
        lambda v: utopia_rsw(v, ts.rest.tar, ts.rest.sf, ff,
                             interpret=interpret))
    rows = []
    baseline_acc = None
    for name, fn in backends.items():
        us = time_us(fn, vpns)
        derived = f"batch={len(vpns)}"
        out = fn(vpns)
        if hasattr(out, "accesses"):
            acc = float(out.accesses.mean())
            byt = float(out.bytes_touched.mean())
            derived += f" accesses/req={acc:.2f} bytes/req={byt:.1f}"
            if name == "radix":
                baseline_acc = acc
            if name == "utopia":
                derived += f" rsw_hit={float(out.in_rest.mean()):.2%}"
        rows.append({"name": f"translation/{name}", "us": us,
                     "derived": derived})
    # headline ratio (paper: utopia issues far fewer requests than radix)
    ut = float(translate(ts, vpns).accesses.mean())
    rd = float(translate_radix(None, rtab, vpns).accesses.mean())
    rows.append({"name": "translation/access_reduction_vs_radix", "us": 0.0,
                 "derived": f"utopia={ut:.2f} radix={rd:.2f} "
                            f"reduction={1 - ut / rd:.2%} (paper: fewer "
                            f"serial accesses; 88% fewer mem requests)"})
    return rows


if __name__ == "__main__":
    for r in run():
        print(csv_row(r["name"], r["us"], r["derived"]))
