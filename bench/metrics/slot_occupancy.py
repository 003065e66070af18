from readers import slot_occupancy as read  # noqa: F401
