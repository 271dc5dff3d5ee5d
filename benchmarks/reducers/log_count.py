"""Lines matching `pattern` that the server's log gained between the
window's opening and its closing (by byte offset: the lines carry no
time). The server child runs with JAX_LOG_COMPILES=1."""
import re


def reduce(ctx, spec):
    pattern = re.compile(spec["params"]["pattern"])
    return sum(1 for line in ctx["server_log_window"].splitlines()
               if pattern.search(line))
