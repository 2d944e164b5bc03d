"""What decides `correct`: every reason a run's output is not to be
trusted, as a list.  An empty list is a correct run."""

from __future__ import annotations


def reasons(obs: dict, config: dict, chips: int, rehearse: bool,
            parent_imported_jax: bool) -> list:
    bad = []
    tolerance = config["reference"]["loss_tolerance"]
    got, want = obs["losses_first"], obs["reference_losses"]
    for step, (g, w) in enumerate(zip(got, want)):
        if not abs(g - w) <= tolerance:
            bad.append(f"loss at step {step} is {g}, the plain reference's "
                       f"is {w}: further apart than {tolerance}")
    if len(got) != len(want) or not got:
        bad.append(f"{len(got)} losses against {len(want)} of the reference")
    if obs["window_nonfinite"]:
        bad.append(f"{obs['window_nonfinite']} losses in the window are "
                   f"not finite")
    if not obs["window_steps"]:
        bad.append("no step completed inside the window")
    if obs["attention_fallbacks"]:
        bad.append(f"attention fell back to the O(S^2) reference: "
                   f"{obs['attention_fallbacks'][0]}")
    device = obs["device"]
    if rehearse:
        if device["platform"] != "cpu":
            bad.append(f"a rehearsal runs on the cpu, not on "
                       f"{device['platform']!r}")
    else:
        if device["platform"] != "tpu":
            bad.append(f"platform is {device['platform']!r}, not 'tpu'")
        if device["kind"] != config["device_kind"]:
            bad.append(f"device kind is {device['kind']!r}, the "
                       f"configuration is sized for "
                       f"{config['device_kind']!r}")
        if device["count"] != chips:
            bad.append(f"the cell asks for {chips} chips, the worker sees "
                       f"{device['count']}")
    if parent_imported_jax:
        bad.append("the parent process imported jax")
    if obs["compiles_in_window"]:
        bad.append(f"compiled inside the window: "
                   f"{sorted(set(obs['compiles_in_window']))}")
    return bad
