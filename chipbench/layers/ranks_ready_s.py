"""L5 launch: from the runner's spawn of the gang to every rank past the
start-up barrier and the first worker past INIT and seeding (the
runner's and the workers' host clocks, one monotonic clock on the host)."""


def read(run):
    ready = [res["chipbench"]["marks"].get("init_seed_done",
                                           res["chipbench"]["marks"]["past_barrier"])
             for res in run["results"].values()]
    return max(ready) - run["t_spawn"]
