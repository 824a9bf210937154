"""init_coord_share: the share of the window's race coordinates that the
wide inits paid, %: the ``coord_ops`` of the ``race.init`` spans over
those plus the ``coord_ops`` of the ``race.epoch`` spans. The pow2 pad
rows of a plane group pay their init and then retire."""


def read(run):
    init = [e["attrs"]["coord_ops"] for e in run.events
            if e.get("name") == "race.init"]
    if not init:
        return None
    epochs = sum(e["attrs"]["coord_ops"] for e in run.events
                 if e.get("name") == "race.epoch")
    total = sum(init) + epochs
    return 100.0 * sum(init) / total if total > 0 else None
