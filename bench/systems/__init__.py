"""Cell drivers, one module per system, found by the configuration's
`system` key: each sets a cell up, runs its window and checks it."""
