"""Block program: device ms of the jitted block program per wave executed
in the traced window."""

# program names as the trace shows them: ``engine._scan_block`` on one
# chip, the shard_map body of ``dist_engine._block_fn`` on the mesh
PROGRAMS = ("jit__scan_block(", "jit_node_fn(")


def read(ctx):
    t = ctx.trace
    if t is None or not t.waves:
        return None
    s = t.module_s(PROGRAMS)
    return s / t.waves * 1e3 if s > 0 else None
