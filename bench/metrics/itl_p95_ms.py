from readers import itl_p95_ms as read  # noqa: F401
