"""Share of the traced window the fullest chip spent in the halo
exchange's all-to-all: the summed device time of its ops whose opcode, as
the trace prints it, is ``all-to-all`` (or an async ``all-to-all-start``
/ ``all-to-all-done``) or whose instruction is named so, over the window.
On one chip the exchange is a vmapped copy with no such op, and the
reader finds nothing."""
import re

# "<program>/%all-to-all.3 all-to-all f32[...]": instruction, opcode,
# shape; either the opcode or the instruction's name says all-to-all
_A2A = re.compile(r"(?:^|/)%?(?:all-to-all[\w.\-]*(?: |$)|[\w.\-]+ "
                  r"all-to-all(?:-start|-done)? )")


def read(ctx):
    if ctx.dev is None or ctx.trace.window_ns <= 0:
        return None
    ns = ctx.trace.op_ns(ctx.dev, lambda n: _A2A.search(n) is not None)
    if ns <= 0:
        return None
    return 100.0 * ns / ctx.trace.window_ns
