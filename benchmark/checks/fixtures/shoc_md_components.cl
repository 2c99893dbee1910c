/* compute_lj_force (benchmark/configs/shoc_md.cl) as a port had to write it before
 * the kernel language had vector types: every float4 taken apart into its four floats.
 * Statement for statement the same arithmetic in the same order, so the two kernels
 * give the same BYTES (tests/test_vector_types.py; md_step_on_chip.py times both). */
__kernel void compute_lj_force(__global float *force3, __global float *position,
                               const int neighCount, __global int *neighList,
                               const float cutsq, const float lj1, const float lj2,
                               const int inum)
{
    uint idx = get_global_id(0);
    float iposx = position[4 * idx];
    float iposy = position[4 * idx + 1];
    float iposz = position[4 * idx + 2];
    float fx = 0.0f;
    float fy = 0.0f;
    float fz = 0.0f;
    float fw = 0.0f;
    int j = 0;
    while (j < neighCount) {
        int jidx = neighList[j * inum + idx];
        float delx = iposx - position[4 * jidx];
        float dely = iposy - position[4 * jidx + 1];
        float delz = iposz - position[4 * jidx + 2];
        float r2inv = delx * delx + dely * dely + delz * delz;
        if (r2inv < cutsq) {
            r2inv = 1.0f / r2inv;
            float r6inv = r2inv * r2inv * r2inv;
            float force = r2inv * r6inv * (lj1 * r6inv - lj2);
            fx += delx * force;  fy += dely * force;  fz += delz * force;
        }
        j++;
    }
    force3[4 * idx] = fx;
    force3[4 * idx + 1] = fy;
    force3[4 * idx + 2] = fz;
    force3[4 * idx + 3] = fw;
}
