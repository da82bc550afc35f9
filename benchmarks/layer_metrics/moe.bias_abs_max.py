"""How far the router's load bias has moved: the program's own counter
``moe.bias_abs_max`` (the largest ``|b_e|`` over the expert layers, on
the parameters a step STARTS from) at the window's last step.  No
gradient reaches the bias: it moves only by the rule the loss hands
the train step (``u`` towards the mean load, every step), so a program
whose step does not apply that rule reads 0, and nothing reads over
``u x steps``."""

NAME = "moe.bias_abs_max"
UNIT = "bias"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    steps = {s["step"] for s in run.report["window"]["steps"]}
    found = [
        (e["step"], e[NAME]) for e in run.of("train_step")
        if e.get("step") in steps and NAME in e
    ]
    if not found:
        return None
    step, value = max(found)
    rate = run.config["recipe"]["bias_update_rate"]
    run.note(
        f"router bias: largest |b| {value:.4f} entering step {step}; "
        f"the rule moves an expert by {rate} a step, so at most "
        f"{rate * step:.4f} by then"
    )
    return value
