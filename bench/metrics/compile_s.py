"""Seconds of set-up spent on the plan: `compile_plan` (with its kernel fit
check) and the warm-up turnover of every slot, on the harness's clock."""


def read(ctx):
    return ctx.compile_s
