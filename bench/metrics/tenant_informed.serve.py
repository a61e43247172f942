"""Share of the window's decided requests whose tenant's row held at least
one admitted deployment, in %, from the engine's ``tenant_lookups_total``
counter (``layer.tenant_lookups``, set by ``drivers/served_tenant.py``;
a cell without tenants has none)."""


def read(layer):
    lookups = getattr(layer, "tenant_lookups", None)
    if not lookups:
        return None
    n = lookups["informed"] + lookups["cold"]
    if n <= 0:
        return None
    return 100.0 * lookups["informed"] / n
