"""Share of the pages committed in window layers that were written over a
page that had slid out of its lane's window, over the untraced measured
loop: the program's counter ``cgx.serve.window.pages_recycled`` over
``cgx.serve.window.pages_committed``. 0 would say the traffic never passes a
window (every ring still filling). Nothing for a program without the
counters or a loop that committed no window page."""


def read(ctx):
    counters = ctx.get("counters")
    if not counters:
        return None
    start, end = counters["start"], counters["end"]
    made, over = ("cgx.serve.window.pages_committed",
                  "cgx.serve.window.pages_recycled")
    if made not in end:
        return None
    committed = end[made] - start.get(made, 0.0)
    if committed <= 0:
        return None
    return 100.0 * (end.get(over, 0.0) - start.get(over, 0.0)) / committed
