"""The pass at which a token would leave a looped decoder, by its exit gates:
the mean over the traced window's decode steps and active lanes of ``sum_t t
x p_t``, ``p_t`` the exit distribution the program computes on the served
path (``cgx.serve.loop.exit_mass.<t>``, thousandths summed over the lanes,
``t`` from 1; the driver leaves the traced steps' sums). Between 1 and the
configuration's passes. It is the gates' say alone: at the published
threshold of 1.0 every token takes every pass whatever this reads, and a
later change that lets tokens leave early starts from this number. Nothing
for a driver or a program that leaves no such sums."""


def read(ctx):
    loop = ctx["loop"]
    mass = []
    while f"traced_exit_mass_{len(mass) + 1}" in loop:
        mass.append(loop[f"traced_exit_mass_{len(mass) + 1}"])
    if not mass or sum(mass) <= 0:
        return None
    return sum((t + 1) * m for t, m in enumerate(mass)) / sum(mass)
