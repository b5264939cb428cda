"""Loader `grid_wide`: `grid` itself (the same generate -> reference ->
ingest, line for line: it calls `grid.load`), after one question to the
program, as `grid_on_mirror` asks one.

A dashboard at Grafana's default range asks 721 windows of rows that hold
2,304 samples: a fused leaf of 2,304 padded columns by 768 padded windows,
six window tiles where every older cell has one.  Before it generates
anything the loader hands the program's own entry for one such leaf
(`ops/pallas_fused.fused_rate_groupsum`: what the mesh executor calls once a
device) a dense, counter-corrected working set of zeros on the cell's first
grid and waits for the answer.  A program whose kernel does not lower at
that shape (before PR 44 the boundary gathers failed in the chip's compiler
past 128 windows, and the leaf fell to the general XLA path: seconds a
request, compiles inside the window), or that cannot be asked, is not
serving this deployment from the fused leaf: the run ends here, in seconds.
On the CPU (`--rehearse`) the kernel runs interpreted, which lowers nothing
and so turns nobody away.
"""
import numpy as np

ROWS, GROUPS = 256, 8       # one block of series: the question is the shape's


def require_fused_wide_leaf(cfg, plan):
    import jax.numpy as jnp
    from filodb_tpu.ops import pallas_fused as pf
    interpret = pf.kernel_mode()
    if interpret is None:
        raise RuntimeError("this process may not run the fused kernel at all")
    T = cfg["samples"]
    ts_row = np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    end = ts_row[-1] - plan.phases[0] * 1000
    wends = end - np.arange(plan.n_windows, dtype=np.int64)[::-1] \
        * plan.step_s * 1000
    try:
        fused = pf.build_plan(ts_row, wends, plan.range_s * 1000)
        sums, _ = pf.fused_rate_groupsum(
            jnp.zeros((ROWS, T), jnp.float32), jnp.zeros((ROWS,), jnp.float32),
            (np.arange(ROWS) % GROUPS).astype(np.int32), fused, GROUPS,
            "rate", precorrected=True, interpret=interpret)
        sums.block_until_ready()
    except Exception as e:      # noqa: BLE001 - whatever the compiler says
        raise RuntimeError(
            f"a dense rate leaf of {T} samples by {plan.n_windows} windows "
            f"is not fused by this program ({type(e).__name__}: "
            f"{str(e)[:300]}): this deployment's dashboards must be "
            "answered by the fused leaf, not by the general XLA path") from e


def load(server, cfg, plan, seed, control, spans, find):
    """`grid.load`, after the question above.  Returns (Reference, series
    per shard)."""
    require_fused_wide_leaf(cfg, plan)
    return find("loaders", "grid").load(
        server, cfg, plan, seed, control, spans, find)
