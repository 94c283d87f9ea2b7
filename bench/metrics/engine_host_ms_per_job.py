"""Host time of one ``Simulation.run`` call outside its device scan
(state set-up, buffer copies, dispatch), ms: the host clock around the
call minus ``SimResult.wall_time_s``."""


def read(ctx):
    job = ctx["job"]
    return (job.call_s - job.engine_wall_s) * 1e3
