"""Weight-movement data-plane benchmark: bytes per round, per codec.

The reference ships the FULL model through RedisAI every K-AVG round
(ml/pkg/model/model.go:135-161 — 2N full-model transfers per sync); the
kubeml-tpu counterpart is the PS<->runner weight exchange the engine/dataplane
codecs compress. This harness measures that exchange honestly on whatever box
it runs on: a real K-AVG training loop where EVERY round's reference weights
travel encoder -> payload -> decoder exactly as they would cross the wire, so
the bytes are real, the compression ratio is real, and — because training
CONTINUES from the receiver-visible chain (the encoder's synced state mirrors
the decoder bit-for-bit) — the final loss shows whether the lossy codec
stayed convergent.

Three rows (raw / delta / delta-int8) append to
``results/dataplane_bench.jsonl``, plus one ``projected-e2e`` row per lossy
codec: the measured bytes-per-round reduction applied to the round-5
recorded staging budget (54.8% of each end-to-end round is staging at
83 MB/s over ~3.2 MB/round — results/profile_demo.jsonl), giving the
end-to-end samples/sec the r05 chip run would sustain if the weight channel
shipped this codec's bytes. The projection is labeled as such; the row is
shaped like a bench record so ``scripts/bench_compare.py`` gates it against
the r05 row — a codec that REGRESSES bytes projects an e2e below baseline and
fails the gate loudly (scripts/dataplane_bench.sh).
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from ..engine.dataplane import CODECS, DeltaDecoder, DeltaEncoder

# the round-5 driver row's gap (results/profile_demo.jsonl, recorded-chip-gap):
# the baseline this harness projects codec wins onto
R05_DEVICE_SPS = 32791.3
R05_E2E_SPS = 14810.5
R05_STAGING_BW_BPS = 83297835.0  # achieved staging bandwidth on the r05 link
R05_SAMPLES_PER_ROUND = 1024.0  # n=1 x k=8 x batch=128


def _train_with_codec(codec: str, rounds: int = 12, seed: int = 0,
                      n_workers: int = 1, k: int = 4,
                      batch: int = 32) -> Dict:
    """One measured row: K-AVG training where each round's reference weights
    round-trip through ``codec`` and training continues from the DECODED
    tree — the full PS<->runner feedback loop, error feedback included."""
    import jax

    from ..engine.kavg import KAvgTrainer
    from .harness import flagship, make_synthetic_model

    fs = flagship()
    model = make_synthetic_model(fs.module, "dataplane-synth",
                                 uint8_inputs=True)
    trainer = KAvgTrainer(model, precision="bf16", donate=False)
    rng = jax.random.PRNGKey(seed)
    r = np.random.default_rng(seed)
    x = r.integers(0, 256, size=(n_workers, k, batch, *fs.sample_shape)
                   ).astype(np.uint8)
    y = r.integers(0, fs.num_classes, size=(n_workers, k, batch)
                   ).astype(np.int64)
    mask = np.ones((n_workers, k, batch), np.float32)
    variables = trainer.init_variables(rng, x[0, 0], n_workers)

    enc = DeltaEncoder(codec)
    dec = DeltaDecoder()
    payload_bytes: List[int] = []
    dense_bytes = 0
    encode_s = 0.0
    losses: List[float] = []
    for i in range(rounds):
        variables, loss = trainer.sync_round(
            variables, x, y, mask, jax.random.fold_in(rng, i), lr=0.05)
        losses.append(float(loss))
        ref = trainer.reference_variables(variables)
        dense_bytes = sum(a.nbytes for a in jax.tree.leaves(ref))
        t0 = time.perf_counter()
        payload = enc.encode(ref, i + 1)
        encode_s += time.perf_counter() - t0
        payload_bytes.append(len(payload))
        decoded, _v = dec.decode(payload)
        # training continues from what the RECEIVER holds — for lossy codecs
        # this is the convergence question itself (error feedback must keep
        # the chain on track); for raw/delta it is a bit-exact no-op
        variables = trainer.place_reference(decoded, n_workers)
    # steady-state bytes/round: skip the first payload (always a full
    # snapshot — the chain bootstrap, paid once per runner lifetime)
    steady = payload_bytes[1:] or payload_bytes
    mismatch = _max_mismatch(enc, dec)
    return {
        "kind": "dataplane-codec",
        "codec": codec,
        "model": fs.name,
        "rounds": rounds,
        "dense_bytes_per_round": int(dense_bytes),
        "first_payload_bytes": int(payload_bytes[0]),
        "bytes_per_round": float(np.mean(steady)),
        "compression_ratio": (float(dense_bytes / np.mean(steady))
                              if steady and np.mean(steady) > 0 else None),
        "encode_seconds_per_round": encode_s / rounds,
        "final_loss": losses[-1],
        "loss_trajectory": [round(l, 5) for l in losses],
        # encoder/decoder chain divergence (must be 0 — the convergence
        # argument rests on the mirrors staying bit-identical)
        "chain_mismatch": mismatch,
    }


def _max_mismatch(enc: DeltaEncoder, dec: DeltaDecoder) -> float:
    worst = 0.0
    for key, a in enc.synced.items():
        b = dec.tree.get(key)
        if b is None or a.shape != b.shape:
            return float("inf")
        if a.size:
            worst = max(worst, float(np.max(np.abs(
                a.astype(np.float64) - b.astype(np.float64)))))
    return worst


def project_e2e(bytes_per_round: float, raw_bytes_per_round: float,
                codec: str) -> Dict:
    """The r05 chip run's end-to-end throughput if the weight channel
    shipped ``codec``'s bytes: the staging budget per round shrinks by the
    measured byte ratio at the recorded staging bandwidth. Labeled a
    PROJECTION — the real number comes from the next chip bench — but
    shaped like a bench record so bench_compare can gate it."""
    t_device = R05_SAMPLES_PER_ROUND / R05_DEVICE_SPS
    t_e2e = R05_SAMPLES_PER_ROUND / R05_E2E_SPS
    staging_s = t_e2e - t_device
    ratio = bytes_per_round / max(raw_bytes_per_round, 1.0)
    staging_after = staging_s * ratio
    e2e_after = R05_SAMPLES_PER_ROUND / (t_device + staging_after)
    return {
        "kind": "projected-e2e",
        "codec": codec,
        "metric": "resnet18-cifar10-kavg-train-throughput",
        "value": R05_DEVICE_SPS,  # device throughput is untouched
        "unit": "samples/sec",
        "end_to_end": round(e2e_after, 1),
        "staging_share_after": round(staging_after / (t_device + staging_after), 4),
        "byte_ratio_vs_raw": round(ratio, 4),
        "baseline_e2e": R05_E2E_SPS,
        "note": "PROJECTION: r05 recorded staging budget scaled by the "
                "codec's measured bytes-per-round ratio at the recorded "
                "staging bandwidth; device number carried over unchanged",
    }


def run(out_path: Optional[Path] = None, rounds: int = 12) -> List[Dict]:
    """All codec rows + projections, appended to ``out_path`` (one JSON line
    each) when given. Returns the rows."""
    rows: List[Dict] = []
    ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    for codec in CODECS:
        row = _train_with_codec(codec, rounds=rounds)
        row["ts"] = ts
        rows.append(row)
    raw_bpr = next(r["bytes_per_round"] for r in rows if r["codec"] == "raw")
    for codec in ("delta", "delta-int8"):
        row = next(r for r in rows if r["codec"] == codec)
        proj = project_e2e(row["bytes_per_round"], raw_bpr, codec)
        proj["ts"] = ts
        rows.append(proj)
    if out_path is not None:
        out_path = Path(out_path)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        with out_path.open("a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return rows


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="per-round weight-exchange bytes by dataplane codec")
    parser.add_argument("--rounds", type=int, default=12)
    parser.add_argument(
        "--out", default=str(Path(__file__).resolve().parents[2]
                             / "results" / "dataplane_bench.jsonl"))
    args = parser.parse_args(argv)
    rows = run(Path(args.out), rounds=args.rounds)
    for row in rows:
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
