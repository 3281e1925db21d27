"""Paper Fig. 2 (top): monolithic GEMM vs task-fragmented (RTM) GEMM.

MTB-GEMM = one XLA dot (the vendor-BLAS analogue: XLA:CPU's own cache-aware
single kernel).  RTM-GEMM = the same product fragmented into b×b tile tasks
(paper §3.4): ``C_ij = Σ_k A_ik·B_kj`` with one dot per task.  The paper's
finding — fragmentation wrecks a highly-parallel BLAS-3 op — reproduces on
XLA: the fragmented form defeats the fused/tiled monolithic kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, gflops, random_matrix, time_fn


def _rtm_gemm(a, b, tile: int):
    n = a.shape[0]
    c = jnp.zeros_like(a)
    for i in range(0, n, tile):
        for j in range(0, n, tile):
            acc = jnp.zeros((tile, tile), a.dtype)
            for k in range(0, n, tile):
                acc = acc + a[i:i+tile, k:k+tile] @ b[k:k+tile, j:j+tile]
            c = c.at[i:i+tile, j:j+tile].set(acc)
    return c


def run(sizes=(512, 1024), tile=128):
    rows = []
    for n in sizes:
        a, b = random_matrix(n, 0), random_matrix(n, 1)
        flops = 2.0 * n ** 3

        mono = jax.jit(jnp.matmul)
        t = time_fn(mono, a, b)
        rows.append(emit(f"gemm_mtb_n{n}", t, f"{gflops(flops, t):.2f}GFLOPS"))

        rtm = jax.jit(lambda a, b: _rtm_gemm(a, b, tile))
        t = time_fn(rtm, a, b)
        rows.append(emit(f"gemm_rtm_n{n}_b{tile}", t,
                         f"{gflops(flops, t):.2f}GFLOPS"))
    return rows


def run_kernels(n: int = 64, b: int = 16, gemm_n: int = 256):
    """BENCH_kernels.json rows (ISSUE 8): the Pallas kernel layer.

    Three families — the BLIS-GEMM blocking sweep (§9-derived candidates
    from :func:`repro.tune.model.gemm_blocks`), traced-vs-Pallas panel
    kernels, and fused-vs-composed PU(k+1).  On CPU the Pallas kernels run
    in *interpret mode*, whose wall-clock is Python-evaluation time, not
    kernel time: those rows carry ``derived="interpret"`` (no GFLOPS
    figure) and exist to pin the trajectory schema and the candidate set;
    on a TPU backend the same code path emits real GFLOPS.
    """
    import functools

    import numpy as np

    from repro.kernels import ops as kops
    from repro.kernels import panels, ref
    from repro.tune.model import gemm_blocks

    rows = []

    # --- BLIS five-loop GEMM, blocking sweep -------------------------------
    # gemm_n is larger than the panel n so the §9 targets produce *distinct*
    # blockings (at small n every target collapses to one aligned shape).
    a, bm_ = random_matrix(gemm_n, 0), random_matrix(gemm_n, 1)
    interp = kops._interpret(a)
    flops = 2.0 * gemm_n ** 3
    kbs = [gemm_blocks(gemm_n, gemm_n, gemm_n, a.dtype)]
    for target in ((256, 256, 256), (128, 128, 128)):
        kb = gemm_blocks(gemm_n, gemm_n, gemm_n, a.dtype, target=target)
        if kb not in kbs:
            kbs.append(kb)
    for kb in kbs:
        fn = functools.partial(kops.gemm, blocks=kb)
        t = time_fn(fn, a, bm_)
        d = "interpret" if interp else f"{gflops(flops, t):.2f}GFLOPS"
        rows.append(emit(
            f"kgemm_blis_bm{kb[0]}x{kb[1]}x{kb[2]}_n{gemm_n}", t, d))

    # --- panel kernels: traced (pure-XLA) vs Pallas (VMEM-resident) --------
    panel = random_matrix(n, 2)[:, :b]
    t = time_fn(panels.TRACED_PANELS["lu"], panel)
    rows.append(emit(f"kpanel_lu_traced_n{n}_b{b}", t, "traced"))
    t = time_fn(kops.lu_panel, panel)
    rows.append(emit(f"kpanel_lu_pallas_n{n}_b{b}", t,
                     "interpret" if interp else "pallas"))
    t = time_fn(panels.TRACED_PANELS["qr"], panel)
    rows.append(emit(f"kpanel_qr_traced_n{n}_b{b}", t, "traced"))
    t = time_fn(kops.qr_panel, panel)
    rows.append(emit(f"kpanel_qr_pallas_n{n}_b{b}", t,
                     "interpret" if interp else "pallas"))
    block = random_matrix(n, 3)
    t = time_fn(lambda x: panels.qrcp_panel(x, b), block)
    rows.append(emit(f"kpanel_qrcp_traced_n{n}_b{b}", t, "traced"))
    t = time_fn(lambda x: kops.qrcp_panel(x, b), block)
    rows.append(emit(f"kpanel_qrcp_pallas_n{n}_b{b}", t,
                     "interpret" if interp else "pallas"))
    hb = max(b // 2, 4)
    t = time_fn(lambda x: panels.hessenberg_panel(x, 0, hb), block)
    rows.append(emit(f"kpanel_hessenberg_traced_n{n}_b{hb}", t, "traced"))
    t = time_fn(lambda x: kops.hessenberg_panel(x, 0, hb), block)
    rows.append(emit(f"kpanel_hessenberg_pallas_n{n}_b{hb}", t,
                     "interpret" if interp else "pallas"))

    # --- PU(k+1): fused single-kernel vs composed TRSM→GEMM→factor ---------
    m = n - b
    rng = np.random.default_rng(4)
    l11 = jnp.asarray(np.tril(rng.standard_normal((b, b)), -1)
                      + np.eye(b), jnp.float32)
    l21 = jnp.asarray(0.1 * rng.standard_normal((m, b)), jnp.float32)
    a1l = jnp.asarray(rng.standard_normal((b, b)), jnp.float32)
    a2l = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
    t = time_fn(kops.fused_lu_panel_update, l11, l21, a1l, a2l)
    rows.append(emit(f"kpu_lu_fused_n{n}_b{b}", t,
                     "interpret" if interp else "pallas"))
    t = time_fn(ref.fused_lu_panel_update, l11, l21, a1l, a2l)
    rows.append(emit(f"kpu_lu_composed_n{n}_b{b}", t, "composed"))

    g = rng.standard_normal((n, n)).astype(np.float32)
    spd = g @ g.T + 2 * n * np.eye(n, dtype=np.float32)
    lrow = jnp.asarray(0.1 * rng.standard_normal((b, b)), jnp.float32)
    cl21 = jnp.asarray(0.1 * rng.standard_normal((m, b)), jnp.float32)
    # first b rows = the PD principal minor spd[:b, :b] (the diag block the
    # fused kernel factors with sqrt); small lrow/l21 keep it PD post-update
    cpanel = jnp.asarray(spd[:m, :b], jnp.float32)
    t = time_fn(kops.fused_cholesky_panel_update, lrow, cl21, cpanel)
    rows.append(emit(f"kpu_cholesky_fused_n{n}_b{b}", t,
                     "interpret" if interp else "pallas"))
    t = time_fn(ref.fused_cholesky_panel_update, lrow, cl21, cpanel)
    rows.append(emit(f"kpu_cholesky_composed_n{n}_b{b}", t, "composed"))
    return rows


if __name__ == "__main__":
    run()
