"""Share of the window's kernel dispatches that the numpy backend served
(``frame/backend.py``'s ``served_counts()``), over all dispatches."""


def read(run):
    total = sum(run.served.values())
    if not total:
        return None
    return sum(n for k, n in run.served.items() if k.endswith("|numpy")) / total
