"""model - eksml_tpu/models/lm: the pass at which the halting gate's
exit distribution expects a position to leave, ``sum_t t p_t`` with
``p_t`` the batch mean of pass t's exit probability (1 = every position
would leave after the first pass, ``total_ut_steps`` = none before the
last; a fresh gate reads 1.875 at four passes).  Mean over the window's
``loop_exit`` spans, which carry the step's ``loop_exit_p<t>`` counters
as ``args`` at log steps."""


def read(ctx):
    passes = []
    for ev in ctx.spans:
        if ev.get("name") != "loop_exit":
            continue
        p = {int(k[len("loop_exit_p"):]): v
             for k, v in ev.get("args", {}).items()
             if k.startswith("loop_exit_p")}
        if p:
            passes.append(sum(t * v for t, v in p.items()))
    return sum(passes) / len(passes) if passes else None
