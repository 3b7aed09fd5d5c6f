"""What one grouped aggregate over a table needs from the device, from the
table's rows and the statement's groups and aggregates (the deployment's
`kernel_shapes`).

Counted for the algorithm and not for a layout: a DISPATCH reads each column
a statement names once, whatever its riders number, at the narrowest
whole-byte encoding of TPC-H Q1's six (quantity 1, discount 1, tax 1, the
two flags 1, ship date 2, extended price 3: 9 bytes a row; counting the
int32 planes a program may hold them in would let one that narrows them read
past 100%), and every statement writes its groups' aggregates as 8-byte
values; twelve operations a (statement, row) cover the comparison, the group
id and six multiply-adds. A kernel that sums limbs through the MXU and one
that carries int32 limbs are judged on this one need.
"""

MODULE = r"^jit_grouped_aggregate"
ROW_BYTES = 9
ROW_OPS = 12.0


def need(shapes: dict, statements: float, dispatches: float) -> dict:
    out = statements * shapes["groups"] * shapes["aggregates"] * 8
    return {"flops": statements * shapes["rows"] * ROW_OPS, "bytes": dispatches * shapes["rows"] * ROW_BYTES + out}
