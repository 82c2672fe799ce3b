"""Key chooser `next`: the key of the next record in insert order (a
write's key; later `latest` draws count it)."""


def draw(entry, keys):
    v = keys.value_of(len(keys.values) + keys.written)
    keys.written += 1
    return (v,)
