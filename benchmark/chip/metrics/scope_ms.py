"""Device milliseconds a step under one of the program's own names: the self
time (``trace_reduce.self_seconds``) of the traced window's ops whose scope
path holds ``prefix`` or a scope inside it (``mx.moe`` holds ``mx.moe.route``
and ``mx.moe.experts``; ``mx.mamba2/mx.ssd`` is under ``mx.mamba2`` and under
``mx.ssd``), over the window's steps. ``rest`` is what is under none of the
prefixes the cell's other metrics read (their modules' ``PREFIX``): head,
loss, embedding, optimizer, casts, and every op the compiler made without an
``op_name`` (layout copies, the ends of asynchronous copies, fusions of its
own), so that a cell's outermost scope metrics and its rest add up to the
device's busy time a step. Nothing where the trace has no op under the
prefix, or names no scope at all."""


def under(path, prefix):
    return any(scope == prefix or scope.startswith(prefix + ".")
               for scope in path.split("/"))


def _ms(run, keep):
    trace = run["trace"]
    if trace:
        seconds = sum(s for path, s in trace["seconds_by_scope"].items()
                      if keep(path))
        if seconds:
            return 1e3 * seconds / trace["steps"]


def read(run, prefix):
    return _ms(run, lambda path: under(path, prefix))


def rest(run):
    prefixes = [m.PREFIX for m in run["readers"].values()
                if hasattr(m, "PREFIX")]
    if prefixes and read(run, "mx") is not None:  # some op names a scope
        return _ms(run, lambda path: not any(under(path, p)
                                             for p in prefixes))
