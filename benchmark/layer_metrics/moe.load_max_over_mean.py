"""model: the largest expert's routes over the mean, in the worst layer, as
the step's first dispatch reported it (``moe_load_max_over_mean`` of
``llama_loss_fn``'s report; worker.json keeps it under
``reference.program``). 1.0 is an even load. On one chip it is a health
counter, not a mover: a dropless layer does the same ``T x k`` rows of
work at any skew (p90 209.39-209.48 ms over loads of 1.76-2.73; my chip
run, PR 27). It moves step_ms_p90, which it declares, only where the
experts are spread over chips (``ep``) and the fullest expert's chip is
the straggler; that cell is where a skewed mix belongs."""


def read(run):
    return (run.worker.get("reference", {}).get("program", {})
            .get("moe_load_max_over_mean"))
