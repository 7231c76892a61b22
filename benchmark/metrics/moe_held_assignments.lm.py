"""Assignments a step that landed on the held experts, summed over the
expert layers, the mean over the window's steps (the step's own
`moe/<layer>/held_assignments`); tokens x experts per token x held / all
experts a layer if routing were even."""


def read(observed):
    held = observed.get("held_assignments")
    return sum(held.values()) if held else None
