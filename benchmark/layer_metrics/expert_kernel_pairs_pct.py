"""Kernels (ops/expert_mlp.py): of the (token, expert) pairs routed to an
expert this program holds in the window, the share whose SwiGLU ran in the
Pallas kernel that walks the experts HIT and their own rows (weights read
once a row tile from the stacks in place, ``gate`` and ``up`` never in HBM,
rows in and out by index): ``engine.stats["moe_kernel_pairs"]`` over
``["moe_pairs_held"]`` (``rtpu_serve_moe_kernel_pairs_total`` over
``rtpu_serve_moe_pairs_held_total``), grown together from the step's own
``[L, E_held]`` token counts. The program chooses the form from what it can
observe (backend, the weights' dtype, whether ``D`` is whole ``[8, 128]``
tiles and ``F`` whole lanes): 100 % on a TPU over bfloat16 experts of such
widths, 0 % where the three ``lax.ragged_dot`` calls run. Nothing to read
in a program without the counter. Moves tpot_p95_ms."""


def read(run):
    marks = run.get("marks", {})
    start, end = (marks.get(k, {}).get("stats", {}) for k in ("start", "end"))
    if "moe_kernel_pairs" not in end:
        return None
    pairs = end.get("moe_pairs_held", 0) - start.get("moe_pairs_held", 0)
    if not pairs:
        return None
    return 100.0 * (end["moe_kernel_pairs"]
                    - start.get("moe_kernel_pairs", 0)) / pairs
