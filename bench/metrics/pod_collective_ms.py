"""Device time per step of the collectives of the compiled step whose
replica groups span the mesh's ``pod`` axis (``bench/hlo_groups.py``),
async halves included: the traffic that crosses pods, ms."""

from bench import hlo_groups, trace


def read(ctx):
    names = hlo_groups.collectives_over(ctx.step_hlo(), ctx.mesh_shape, "pod")
    return trace.collective_ms_per_step(ctx.summary, names)
