"""Running tasks over the tasks the running dataflows submitted, at the
window's end (the paper's reuse saving; 100% is no reuse)."""


def read(ctx):
    submitted = ctx.session.submitted_task_count
    return 100.0 * ctx.session.running_task_count / submitted if submitted else None
