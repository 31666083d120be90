"""Of a train step's device time, the per cent whose operation the program
can name: 100 x (step - ``unlabelled`` - ``unknown``) / step, over the whole
steps of the traced stretch (``step_scopes``). ``unlabelled`` is the time of
the instructions whose ``op_name`` names no node of the container and no
``<family>:<part>`` scope (what XLA made of nothing the program wrote:
copies, layout changes, a fusion it left without metadata that could
inherit none); ``unknown`` the time under names the step's table does not
hold. A fall says that a change put work where no scope stands, and that
the other scope metrics have stopped seeing it."""

from benchmark.metrics import step_scopes


def read(run):
    read = step_scopes.steps(run)
    if read is None:
        return None
    total = sum(read.seconds.values())
    return 100.0 * (total - read.unlabelled - read.unknown) / total
