from readers import prefill_ms_p50 as read  # noqa: F401
