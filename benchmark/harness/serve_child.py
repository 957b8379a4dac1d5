"""The server child: the app an operator runs, plus what a user of the
framework could add from outside.

``examples/openai-server/main.py`` builds the app (``App`` +
``add_openai_routes``, configured from the process environment), as
``chip_smoke.py``'s ``child_serve`` does. Added here, and nowhere in the
program:

* before the app is built, the configuration file named by
  ``BENCH_CONFIG_FILE`` may register its model: ``base`` with
  ``overrides`` applied, under the configuration's own name;
* SIGUSR1 arms the engine's warm-up fence (``mark_steady_state``), which
  has no HTTP surface;
* ``GET /bench/device``: platform, kind and count as JAX reports them, and
  the peak memory of the fullest chip;
* ``/bench/reference``: the plain reference that the configuration's file
  names (``cells.reference_path``) on this engine's weights. ``GET`` says
  which pieces it can remove, ``POST`` runs it.

This is the only process of a run that imports jax.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import signal
import sys
from typing import Any

# Started as a script: the checkout is not on the path yet.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))

from benchmark.harness.cells import load_file, reference_path  # noqa: E402
from benchmark.harness.server import ENTRY_POINT, check  # noqa: E402


def register_configuration(config: dict) -> None:
    """``dataclasses.replace(get_model(base).config, **overrides)`` under the
    name the file's ``TPU_MODEL`` asks for (the configuration's own), with
    any number of overrides: ``cells.check_cut`` has held them to the cuts
    that the file declares."""
    from gofr_tpu.models.registry import ModelSpec, get_model, register_model

    if not config.get("overrides"):
        return
    base = get_model(config["base"])
    register_model(ModelSpec(
        name=config["env"]["TPU_MODEL"], family=base.family,
        config=dataclasses.replace(base.config, **config["overrides"]),
        init=base.init, eos_token=base.eos_token, forward=base.forward,
    ))
    print(served_line(config), flush=True)  # into the server's log


def served_line(config: dict) -> str:
    """The program's config as the registry now gives it under the name the
    engine will ask for, one line of the server's log: a rehearsal of a cut
    configuration reads from it that the child applied every override."""
    from gofr_tpu.models.registry import get_model

    name = config["env"]["TPU_MODEL"]
    return "benchmark: serving " + json.dumps(
        {"model": name, "config": dataclasses.asdict(get_model(name).config)},
        default=str,
    )


def load_reference(config_path: str, config: dict) -> Any:
    """The configuration's reference module, held to what the harness asks
    of one: ``ABLATIONS`` and ``reference_logprobs``."""
    path = reference_path(config_path, config)
    module = load_file(f"bench_reference_{config['reference']}", path)
    ablations = getattr(module, "ABLATIONS", None)
    check(
        isinstance(ablations, tuple) and ablations
        and all(isinstance(a, str) and a for a in ablations),
        f"{path}: ABLATIONS must be a non-empty tuple of names, the pieces "
        f"the reference can remove; found {ablations!r}",
    )
    check(
        callable(getattr(module, "reference_logprobs", None)),
        f"{path}: no reference_logprobs(engine, sequences, n_prompt, ablate)",
    )
    return module


def engines_of(app: Any) -> list:
    tpu = app.container.tpu
    if hasattr(tpu, "replicas"):
        return [r.engine for r in tpu.replicas if hasattr(r, "engine")]
    return [tpu]


def add_bench_routes(app: Any, reference: Any) -> None:
    from gofr_tpu.http.response import Raw

    @app.get("/bench/device")
    async def device(ctx: Any) -> Raw:  # noqa: ARG001
        import jax

        devices = jax.devices()
        peaks = [
            stats.get("peak_bytes_in_use")
            for d in devices if (stats := d.memory_stats())
        ]
        return Raw({
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                (p for p in peaks if p is not None), default=None
            ),
        }, status=200)

    @app.get("/bench/reference")
    async def ablations(ctx: Any) -> Raw:  # noqa: ARG001
        return Raw({"ablations": list(reference.ABLATIONS)}, status=200)

    @app.post("/bench/reference")
    async def logprobs(ctx: Any) -> Raw:
        """{"sequences": [[ids]], "n_prompt": n, "ablate": ""} -> the plain
        reference's teacher-forced log-probability of every token after
        the first ``n_prompt`` of each sequence; null for a sequence on
        which removing ``ablate`` changes nothing."""
        body = json.loads(ctx.request.raw.body)
        engine = engines_of(app)[0]
        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(
            None, reference.reference_logprobs, engine, body["sequences"],
            int(body["n_prompt"]), body.get("ablate") or "",
        )
        return Raw({"logprobs": out}, status=200)


def main() -> None:
    config_path = os.environ["BENCH_CONFIG_FILE"]
    with open(config_path) as fh:
        config = json.load(fh)
    reference = load_reference(config_path, config)  # before the engine boots
    register_configuration(config)
    app = load_file("openai_server", ENTRY_POINT).main()
    add_bench_routes(app, reference)

    def arm_fence(signum: int, frame: Any) -> None:  # noqa: ARG001
        for engine in engines_of(app):
            engine.mark_steady_state()

    signal.signal(signal.SIGUSR1, arm_fence)
    app.run()


if __name__ == "__main__":
    main()
