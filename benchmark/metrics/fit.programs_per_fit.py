"""Programs the unfused loop dispatched in one fit: the ``programs``
attribute of the window's last ``fit`` stage that carries one (the loop's
own vector programs and what each coordinate says an update of it
dispatches; JAX's one-primitive helpers are not counted: OBSERVABILITY.md).
A count of the program's; repeats exactly. The fused fit, one program a
fit, writes no such attribute: no number."""

from benchmark import stages


def read(ctx):
    for record in reversed(stages.records(ctx, "fit")):
        programs = (getattr(record, "attrs", None) or {}).get("programs")
        if programs is not None:
            return programs
    return None
