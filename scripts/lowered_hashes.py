"""Hash the lowered text of the two serving programs of the tiny models, so
that a refactor of ``models/transformer.py`` or ``serving/programs.py`` can
be shown to leave other configurations' programs as they were: run it in
two checkouts (``python3 scripts/lowered_hashes.py``, on the CPU) and
compare the lines. On the CPU the text holds no path and no Python name but
the jitted function's (verify skill, round 30)."""

from __future__ import annotations

import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

MODELS = ("llama-tiny", "moe-tiny", "looped-tiny", "mla-moe-tiny", "sala-tiny")


def lowered_texts(model: str) -> dict:
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    e = InferenceEngine(
        model, tokenizer=ByteTokenizer(), n_slots=8, max_len=256,
        prefill_chunk=128,
    )
    out = {}
    for rows in e.prefill_rungs:
        row = lambda dtype: np.zeros((rows,), dtype=dtype)  # noqa: E731
        operands = e._prefill_operands(
            np.zeros((rows, e.prefill_chunk), np.int32), row(np.int32),
            row(np.int32), row(np.int32), row(bool), row(bool),
            row(np.float32), row(bool), row(np.float32),
        )
        out[f"prefill_chunk[{rows}]"] = e._prefill_chunk_step.__wrapped__.lower(
            *operands, use_bias=False
        ).as_text()
    jnp = e._jnp
    active, ones = jnp.ones((e.n_slots,), bool), jnp.ones((e.n_slots,), jnp.float32)
    out["decode_window"] = e._decode_window.__wrapped__.lower(
        e.params, e._tokens_dev, e._logps_dev, e.cache, active,
        e._nsteps_dev, ones, active, ones, e._fpen_dev, e._ppen_dev,
        e._pcounts_dev, e._seeds_dev, e._bidx_dev, e._bval_dev,
        e._topi_dev, e._topl_dev, e._aids_dev, k=e.window_k, use_bias=False,
    ).as_text()
    return out


def main() -> None:
    for model in sys.argv[1:] or MODELS:
        for program, text in lowered_texts(model).items():
            print(json.dumps({
                "model": model, "program": program,
                "sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
            }), flush=True)


if __name__ == "__main__":
    main()
