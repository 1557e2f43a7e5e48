"""Mean distinct users per scoring launch of the request queue."""


def read(run):
    users = run.ctx.get("online", {}).get("launch_users")
    if not users:
        return None
    return sum(users) / len(users)
