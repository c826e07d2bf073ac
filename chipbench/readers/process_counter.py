"""Reader ``process_counter``: a counter or sampler of the whole process.

A metric that carries no label — one cell a process, as the Session
executor's are (``/stf/session/run_seconds``,
``/stf/session/await_device_seconds``) — over the window. Params and
arithmetic are the ``counter`` reader's (``metric``, ``stat``, ``scale``;
``labels`` is the empty list). It has a name of its own because the
rehearsals of the serving cells read every ``counter`` metric of a cell
under the served model's name.
"""

from chipbench.readers.counter import read  # noqa: F401
