"""On-chip timings behind PR 34's choice of the grouped product for a stacked
all-held expert layer (PERF.md section 6, PR 34; ROADMAP S5):

    chiprun -- python3 scripts/moe_prefill_forms_probe.py

One expert layer's FFN at ``mixtral-8x7b-d4.batch``'s geometry (2,048 rows of a
prefill step and the 256 of its one-row rung, top 2 of 8 experts of 4,096 x
14,336, the three leaves stacked ``[4, 8, ...]`` weight-only int8 as the engine
holds them), run as the serving step runs it: inside a scan over the layers,
the result added to the stream. The forms:

* ``einsum``: every expert for every row (``_ffn_moe`` without a stack), the
  layer's slice of each leaf the scan's own;
* ``tiles``: ``gofr_tpu.models.transformer.moe_tiled_experts``, the form that
  is kept, at several row tiles (the library's ``EXPERT_ROW_TILE`` is one);
* ``ragged``: ``jax.lax.ragged_dot`` over a bf16 copy of the layer's slice made
  in the step, each row's scale its own expert's, gathered (candidate (b) of
  ISSUE 34; it lost and lives only here).

under three routings: balanced (two distinct experts a token, uniform), the
fullest expert at ``--peak`` times the mean, and every token on the same two
experts; with every row valid and with the cell's 27% of rows holding no
token. Prints rows, validity share, load ratio and milliseconds a layer (host
clock around ``block_until_ready``, the best of three after a warm-up call),
and each form's largest difference from the einsum over the valid rows.

``--compile-v5e`` compiles each form for a described v5e here, without a chip,
and counts the ops of the compiled program that write a whole layer's or a
whole expert's weights (a plane-sized copy); ``--hlo-dir DIR`` also writes each
compiled program's text there. ``--rows 64 --d 64 --f 128
--layers 2`` rehearses on the CPU (never a measurement).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", default="2048,256")
    parser.add_argument("--d", type=int, default=4096)
    parser.add_argument("--f", type=int, default=14336)
    parser.add_argument("--experts", type=int, default=8)
    parser.add_argument("--active", type=int, default=2)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--tiles", default="128,256,512")
    parser.add_argument("--peak", type=float, default=3.3)
    parser.add_argument("--valid", default="1.0,0.73")
    parser.add_argument("--forms", default="einsum,tiles,ragged")
    parser.add_argument("--compile-v5e", action="store_true")
    parser.add_argument("--hlo-dir", help="with --compile-v5e: keep the compiled text here")
    args = parser.parse_args()
    if args.compile_v5e:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import transformer as T
    from gofr_tpu.ops.quant import Q8

    E, k, L, D, F = args.experts, args.active, args.layers, args.d, args.f
    cfg = dataclasses.replace(
        T.TransformerConfig(), d_model=D, d_ff=F, n_layers=L, n_experts=E,
        n_experts_active=k,
    )
    out = lambda **kw: print(json.dumps(kw), flush=True)  # noqa: E731

    # -- the forms: f(x [T, D], idx, gates [T, k], valid [T], layers) -> [T, D]
    def einsum(x, idx, gates, valid, layers):
        weights = jnp.zeros((x.shape[0], E), jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], idx
        ].set(gates)

        def body(x, lp):
            hidden = jax.nn.silu(T._wein("td,edf->tef", x, lp["w_gate"])) * (
                T._wein("td,edf->tef", x, lp["w_up"])
            )
            y = T._wein("tef,efd->ted", hidden, lp["w_down"])
            return x + jnp.einsum("ted,te->td", y, weights.astype(x.dtype)), None

        return jax.lax.scan(body, x, layers)[0]

    def tiles_of(tile):
        def tiles(x, idx, gates, valid, layers):
            def body(x, layer):
                y, _ = T.moe_tiled_experts(
                    x, idx, gates, valid, layers, layer, cfg, tile
                )
                return x + y, None

            return jax.lax.scan(body, x, jnp.arange(L))[0]

        return tiles

    def ragged(x, idx, gates, valid, layers):
        M = x.shape[0] * k
        expert = jnp.where(valid[:, None], idx, E).reshape(M)
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
        row_expert = jnp.minimum(expert[order], E - 1)
        back = jnp.zeros((M,), jnp.int32).at[order].set(jnp.arange(M))
        weights = jnp.where(valid[:, None], gates, 0.0).astype(x.dtype)

        def dot(rows, w):
            y = jax.lax.ragged_dot(rows, w.q.astype(rows.dtype), sizes)
            return (y * w.s[row_expert, 0]).astype(rows.dtype)

        def body(x, lp):
            rows = x[order // k]
            hidden = jax.nn.silu(dot(rows, lp["w_gate"])) * dot(rows, lp["w_up"])
            y = dot(hidden, lp["w_down"])
            y = jnp.where((jnp.arange(M) < jnp.sum(sizes))[:, None], y, 0)
            return x + jnp.einsum(
                "tkd,tk->td", y[back].reshape(-1, k, D), weights
            ), None

        return jax.lax.scan(body, x, layers)[0]

    forms = []  # (label, function)
    for name in args.forms.split(","):
        if name == "tiles":
            forms += [
                (f"tiles_{t}", tiles_of(int(t))) for t in args.tiles.split(",")
            ]
        else:
            forms.append((name, {"einsum": einsum, "ragged": ragged}[name]))

    # -- the weights, as the engine holds them: stacked Q8 leaves
    def leaf_shapes():
        shapes = {"w_gate": (L, E, D, F), "w_up": (L, E, D, F), "w_down": (L, E, F, D)}
        return {
            name: Q8(
                q=jax.ShapeDtypeStruct(shape, jnp.int8),
                s=jax.ShapeDtypeStruct(shape[:2] + (1, shape[3]), jnp.float32),
            ) for name, shape in shapes.items()
        }

    def operand_shapes(n_rows):
        return (
            jax.ShapeDtypeStruct((n_rows, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((n_rows, k), jnp.int32),
            jax.ShapeDtypeStruct((n_rows, k), jnp.float32),
            jax.ShapeDtypeStruct((n_rows,), jnp.bool_),
            leaf_shapes(),
        )

    if args.compile_v5e:
        from jax.experimental import topologies
        from jax.experimental.layout import Format, Layout
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])

        def pinned(sds):  # the layout the real run hands over, not XLA's pick
            return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=Format(
                Layout(major_to_minor=tuple(range(len(sds.shape)))), chip))

        plane = re.compile(
            rf"= (?:s8|bf16)\[(?:{E},)?(?:{D},{F}|{F},{D})\]\S* "
            r"(?!parameter|get-tuple-element|bitcast)(\S+?)\("
        )

        def plane_sized_ops(text):
            """Ops that write a layer's or an expert's weights to memory: those
            of the computations that are no fusion's body (inside a fusion a
            slice of the stack is the product's operand read, not a copy)."""
            found, fused = [], False
            for line in text.splitlines():
                if line and not line[0].isspace():
                    fused = line.startswith("%fused_computation")
                if not fused:
                    found += plane.findall(line)
            return found

        for n_rows in map(int, args.rows.split(",")):
            for label, fn in forms:
                compiled = jax.jit(fn).lower(
                    *jax.tree.map(pinned, operand_shapes(n_rows))
                ).compile()
                text = compiled.as_text()
                mem = compiled.memory_analysis()
                out(what="compiled_for_v5e", form=label, rows=n_rows,
                    plane_sized_ops=sorted(set(plane_sized_ops(text))),
                    n_plane_sized_ops=len(plane_sized_ops(text)),
                    temp_gb=round(mem.temp_size_in_bytes / 1e9, 3))
                if args.hlo_dir:
                    name = f"moe_forms_{label}_{n_rows}.hlo.txt"
                    with open(os.path.join(args.hlo_dir, name), "w") as fh:
                        fh.write(text)
        return 0

    device = jax.devices()[0]
    out(device=device.platform, kind=device.device_kind)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def draw(key):
        def one(key, sds):
            return Q8(
                q=jax.random.randint(key, sds.q.shape, -127, 128, jnp.int8),
                s=jnp.full(sds.s.shape, sds.q.shape[2] ** -0.5 / 73.0, jnp.float32),
            )

        shapes = leaf_shapes()
        return {
            name: one(jax.random.fold_in(key, i), shapes[name])
            for i, name in enumerate(sorted(shapes))
        }

    layers = draw(key)

    def routing(n_rows, kind, rng):
        """idx [T, k] of distinct experts: uniform, the fullest expert at
        ``--peak`` times the mean load, or the same two for every token."""
        if kind == "same_two":
            return np.tile(np.arange(k, dtype=np.int32), (n_rows, 1))
        p = np.full((E,), 1.0 / E)
        if kind == "peaked":  # expert 0 takes peak/E of the routes
            p[:] = (1.0 - args.peak / E * 1.0) / (E - 1)
            p[0] = args.peak / E
        # k distinct experts a token, in proportion to p (Gumbel top-k)
        g = np.log(p)[None, :] + rng.gumbel(size=(n_rows, E))
        return np.argsort(-g, axis=1)[:, :k].astype(np.int32)

    def best(fn, *a):
        jax.block_until_ready(fn(*a))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return min(times) * 1e3

    rng = np.random.default_rng(0)
    for n_rows in map(int, args.rows.split(",")):
        x = (jax.random.normal(key, (n_rows, D), jnp.float32) * 0.5).astype(jnp.bfloat16)
        for share in map(float, args.valid.split(",")):
            # a row of the step holds its tokens first: the head of every
            # 256 (or fewer) positions is valid
            c = min(256, n_rows)
            valid = jnp.asarray((np.arange(n_rows) % c) < round(share * c))
            for kind in ("balanced", "peaked", "same_two"):
                idx = routing(n_rows, kind, rng)
                gates = rng.dirichlet(np.ones(k), size=n_rows).astype(np.float32)
                load = np.bincount(idx[np.asarray(valid)].ravel(), minlength=E)
                operands = (x, jnp.asarray(idx), jnp.asarray(gates), valid, layers)
                line, reference = {}, None
                for label, fn in forms:
                    jitted = jax.jit(fn)
                    line[f"{label}_ms_a_layer"] = round(best(jitted, *operands) / L, 3)
                    y = np.asarray(jitted(*operands).astype(jnp.float32))[np.asarray(valid)]
                    if reference is None:
                        reference = y
                        line["mean_abs"] = float(np.mean(np.abs(y)))
                    else:
                        line[f"{label}_max_abs_diff"] = float(np.max(np.abs(y - reference)))
                out(what="expert_layer", rows=n_rows, valid_share=share,
                    routing=kind, valid_routes=int(load.sum()),
                    load_ratio=round(float(load.max() * E / max(load.sum(), 1)), 3),
                    **line)
    stats = device.memory_stats() or {}
    out(peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
